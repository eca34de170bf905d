"""decode_hbm_roofline (%): the bytes the decode steps of the traced
window needed, at the chip's HBM rate, over the device time of the decode
step program (``jit__step_impl``) in that window (model-step layer).  A
step needs every weight it multiplies whatever the routing
(``mla_moe_counts.step_params``), the weights of the held experts that
took at least one decoding row (the engine's ``moe_experts_active``
counter, summed over layers), and each decoding slot's live latent rows;
all at bfloat16, each read once."""

from bench.lib import mla_moe_counts as mc
from bench.lib import phases

PROGRAM = r"^jit__step_impl\b"


def read(rec):
    if rec.trace is None:
        return None
    secs, n = rec.trace.seconds(PROGRAM, which="modules")
    records = [r for r in phases.records_in(rec.run, *rec.window)
               if r.decode_slots]
    if not n or not records:
        return None
    model = rec.config["model"]
    nbytes = mc.BF16 * (len(records) * mc.step_params(model)
                        + sum(r.moe_experts_active for r in records)
                        * mc.expert_params(model))
    nbytes += sum(mc.latent_attn_work(model, ctx)[1]
                  for s in rec.steps for ctx in s.decode_ctx)
    return 100.0 * nbytes / rec.peaks["hbm_bytes_per_s"] / secs
