"""host_step_ms (ms): the median host self time of a ``step()`` -- its
``engine.step`` span less the ``engine.*.wait`` phases in it, the time in
which this synchronous engine has nothing queued on the chip -- over the
``step()`` calls of the window after the profiler stopped (scheduler
layer).  Read from the engine's own record of each step, so the number is
the untraced one of the same run; the median keeps one long step out."""
import statistics

from bench.lib import phases


def read(rec):
    run = rec.run
    if run.traced is None:
        return None
    ms = [phases.host_ns(r) / 1e6
          for r in phases.records_in(run, run.traced[1], run.window[1])]
    return statistics.median(ms) if ms else None
