"""step_mfu (%): model operations of the tokens processed in the window
(prompt tokens computed and decode tokens, each attending to its live
context), counted from the model's shapes, over the window times the
chip's peak bf16 rate (whole model step)."""

from bench.lib import counts, serve


def read(rec):
    model = rec.config["model"]
    t0, t1 = rec.window
    flops = sum(counts.token_flops(model, counts.live(n, model))
                for s in rec.steps for n in s.decode_ctx)
    flops += sum(counts.prefill_flops(model, a, b)
                 for a, b in serve.prefill_work(rec.run, t0, t1))
    if not flops:
        return None
    return 100.0 * flops / ((t1 - t0) * rec.peaks["bf16_flops_per_s"])
