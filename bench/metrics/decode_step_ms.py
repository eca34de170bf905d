"""decode_step_ms (ms): device time of the decode-step program
(``jit__step_impl``) over its launches in the traced window (model-step
layer)."""

PROGRAM = r"^jit__step_impl\b"


def read(rec):
    if rec.trace is None:
        return None
    secs, n = rec.trace.seconds(PROGRAM, which="modules")
    return 1e3 * secs / n if n else None
