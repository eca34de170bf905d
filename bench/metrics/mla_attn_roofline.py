"""mla_attn_roofline (%): the least time the latent decode attention needs
for the work the model asked of it (each live latent row read once, the
absorbed queries and contexts, and their operations, at the chip's
peaks), over the summed device time of the ``paged_mla_decode`` kernel in
the traced window (kernels layer).  The work counts live tokens from the
benchmark's own record of each decode step, never the pages or lanes the
kernel reads."""

from bench.lib import counts
from bench.lib import mla_moe_counts as mc

KERNEL = r"^paged_mla_decode"


def read(rec):
    if rec.trace is None:
        return None
    secs, n = rec.trace.seconds(KERNEL)
    if not n:
        return None
    need = 0.0
    for s in rec.steps:
        flops = nbytes = 0
        for ctx in s.decode_ctx:
            f, b = mc.latent_attn_work(rec.config["model"], ctx)
            flops += f
            nbytes += b
        if flops:
            need += counts.least_time(flops, nbytes, rec.peaks)[0]
    return 100.0 * need / secs if need else None
