"""paged_attn_roofline (%): the least time the paged decode attention
needs for the work the model asked of it (live K/V rows read, query and
output, and their operations, at the chip's peaks), over the summed
device time of the Pallas paged-decode kernel in the traced window
(kernels layer).  The work counts live tokens from the benchmark's own
record of each decode step, never the pages the kernel walks."""

from bench.lib import counts

KERNEL = r"^paged_decode_attention"


def read(rec):
    if rec.trace is None:
        return None
    secs, n = rec.trace.seconds(KERNEL)
    if not n:
        return None
    model = rec.config["model"]
    need = 0.0
    for s in rec.steps:
        flops = nbytes = 0
        for n in s.decode_ctx:
            f, b = counts.decode_attn_work(model, counts.live(n, model))
            flops += f
            nbytes += b
        if flops:
            need += counts.least_time(flops, nbytes, rec.peaks)[0]
    return 100.0 * need / secs if need else None
