"""mla_moe_step_mfu (%): model operations of the decode tokens of the
window, over the window times the chip's peak bf16 rate (the whole model
step of the latent-attention expert-share cell).  Each decode token
counts its absorbed latent attention at its live context, the weights it
multiplies whatever its routing (``mla_moe_counts.step_params``), and the
rows the held experts took (the engine's ``moe_rows_held`` counter).
Prompt tokens computed in the window are not counted."""

from bench.lib import mla_moe_counts as mc
from bench.lib import phases


def read(rec):
    model = rec.config["model"]
    t0, t1 = rec.window
    flops = sum(mc.decode_flops(model, ctx)
                for s in rec.steps for ctx in s.decode_ctx)
    flops += sum(r.moe_rows_held for r in phases.records_in(rec.run, t0, t1)
                 ) * mc.expert_row_flops(model)
    if not flops:
        return None
    return 100.0 * flops / ((t1 - t0) * rec.peaks["bf16_flops_per_s"])
