"""prefill_ms_per_ktok (ms/ktok): device time of the prefill-chunk
programs (``jit__chunk_impl``) over the prompt tokens they computed
(admitted minus served from shared pages) in the traced window, per
thousand tokens (model-step layer)."""

from bench.lib import serve

PROGRAM = r"^jit__chunk_impl\b"


def read(rec):
    if rec.trace is None:
        return None
    secs, n = rec.trace.seconds(PROGRAM, which="modules")
    tokens = sum(b - a for a, b in serve.prefill_work(rec.run,
                                                       *rec.window))
    return 1e3 * secs / (tokens / 1e3) if n and tokens else None
