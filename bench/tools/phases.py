"""One run of a cell, read through the serving engine's own spans and
step records.

    python bench/tools/phases.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--trace-seconds <s>]

Makes the run ``bench/run.py`` makes, with the same arguments, and prints
what it prints (standard output ends in the same result line);
``--trace-seconds`` traces that much of the window in place of the
benchmark's first ``TRACE_SECONDS`` (a longer trace catches rarer events,
and its per-layer metrics read the longer part).  Then, on standard
error, one JSON line prefixed ``PHASES``:

* ``host_step_ms``: the median host self time of a ``step()`` over the
  window after the profiler stopped (``untraced``: the whole window when
  not tracing) and over the traced part (``traced``), with the step count
  and the summed host self time of each part;
* ``phase_ms``: mean host milliseconds a step spent in each span, over the
  window;
* ``long_steps``: every step over ``LONG_MS``, and the three longest, with
  its phase split, the milliseconds in full garbage collections, what it
  did, and the step before it;
* with ``--trace 1``, ``idle``: the traced window's idle device seconds by
  the innermost host span over most of each gap, with the host spans moved
  onto the device's clock by the measured offset (``offset_ns``: the
  shift, and the bounds it was chosen between) and, beside it, unmoved;
  and ``long_gaps``: each idle gap over ``LONG_GAP_MS`` (the longest
  ``TOP_GAPS``), with the device operations on either side of it, the
  program it lies inside (if any), and the host events over most of it (of any thread, the runtime's among them;
  not the Python tracer's frames).

Needs the chip, like a run.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import run as bench_run  # noqa: E402  (starts the set-up clock)

import json  # noqa: E402
import statistics  # noqa: E402

LONG_MS = 1000.0
LONG_GAP_MS, TOP_GAPS = 10.0, 6


def _ms(ns) -> float:
    return ns / 1e6


def describe(r, t0: float) -> dict:
    """One record, in milliseconds, with its start in seconds after
    ``t0``."""
    return {"at_s": r.t0_ns / 1e9 - t0, "ms": _ms(r.ns),
            "phases": {k: _ms(v) for k, v in sorted(
                r.phase_ns.items(), key=lambda kv: -kv[1])},
            "gc_ms": _ms(r.gc_ns), "decode_slots": r.decode_slots,
            "prefill_rows": r.prefill_rows,
            "prefill_tokens": r.prefill_tokens, "admitted": r.admitted,
            "finished": r.finished, "preempted": r.preempted,
            "cow_copies": r.cow_copies, "pages_live": r.pages_live}


def part(records) -> dict:
    """Step count, median and summed host self time of some records."""
    from bench.lib import phases
    ms = [_ms(phases.host_ns(r)) for r in records]
    return {"steps": len(ms), "median": statistics.median(ms) if ms else None,
            "sum_s": sum(ms) / 1e3}


def report(run, tr, spans) -> dict:
    """The ``PHASES`` line of a finished run (``tr`` and ``spans`` are its
    trace and host spans when traced, else None)."""
    from bench.lib import phases
    from bench.lib import trace as trace_lib
    w0, w1 = run.window
    split = run.traced[1] if run.traced else w0
    window = phases.records_in(run, w0, w1)
    out = {"host_step_ms": {
        "untraced": part(phases.records_in(run, split, w1)),
        "traced": part(phases.records_in(run, w0, split))
        if run.traced else None}}
    total: dict = {}
    for r in window:
        for k, v in r.phase_ns.items():
            total[k] = total.get(k, 0) + v
    out["phase_ms"] = {k: _ms(v) / max(len(window), 1)
                       for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])}
    out["gc_ms"] = sum(_ms(r.gc_ns) for r in window)
    longest = sorted(range(len(window)), key=lambda i: -window[i].ns)
    pick = sorted({i for i in longest[:3]}
                  | {i for i in longest if _ms(window[i].ns) > LONG_MS})
    out["long_steps"] = [
        dict(describe(window[i], w0),
             before=describe(window[i - 1], w0) if i else None)
        for i in pick]
    if tr is not None:
        lo, hi = tr.window()
        dev = sorted(tr.ops)[0]
        gaps = trace_lib.idle_gaps(tr.ops[dev], lo, hi)
        offset = phases.host_offset(spans, tr.modules.get(dev, []))
        out["idle"] = {
            "idle_s": sum(e - s for s, e in gaps) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "offset_ns": offset,
            "by_span": phases.attribute(
                gaps, spans, shift=offset[0] if offset else 0),
            "by_span_unmoved": phases.attribute(gaps, spans)}
    return out


def long_gaps(path: str, tr) -> list:
    """The longest idle gaps of the traced window, each with the device
    operations that end and start it and the host events over at least
    half of it (read from the trace file at ``path``)."""
    from jax.profiler import ProfileData

    from bench.lib import phases
    from bench.lib import trace as trace_lib
    lo, hi = tr.window()
    dev = sorted(tr.ops)[0]
    ops = sorted(tr.ops[dev], key=lambda e: e.start)
    gaps = sorted((g for g in trace_lib.idle_gaps(ops, lo, hi)
                   if g[1] - g[0] > LONG_GAP_MS * 1e6),
                  key=lambda g: g[0] - g[1])[:TOP_GAPS]
    if not gaps:
        return []
    gaps.sort()
    over: list = [[] for _ in gaps]      # (overlap, line, event) per gap
    for plane in ProfileData.from_file(phases.trace_file(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("$"):      # Python tracer frames
                    continue
                s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                for k, (gs, ge) in enumerate(gaps):
                    if min(ge, e) - max(gs, s) > (ge - gs) / 2:
                        over[k].append((min(ge, e) - max(gs, s), line.name,
                                        trace_lib.Event(ev.name, s, e)))
    out = []
    for (gs, ge), host in zip(gaps, over):
        before = max((o for o in ops if o.end <= gs), default=None,
                     key=lambda o: o.end)
        after = next((o for o in ops if o.start >= ge), None)
        host = sorted(host, key=lambda t: -t[0])[:12]
        # a program that runs over the whole gap idled inside itself
        within = [trace_lib.short(m.name)[:60]
                  for m in tr.modules.get(dev, [])
                  if m.start <= gs and ge <= m.end]
        out.append({
            "at_s": (gs - lo) / 1e9, "ms": (ge - gs) / 1e6,
            "op_before": before and trace_lib.short(before.name),
            "op_after": after and trace_lib.short(after.name),
            "inside_program": within,
            "host": [[line, e.name[:100], (e.start - gs) / 1e6,
                      (e.end - e.start) / 1e6] for _, line, e in host]})
    return out


def main(argv=None) -> int:
    import argparse

    from bench.lib import phases, serve
    from bench.lib import trace as trace_lib
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--trace-seconds", type=float, default=None)
    args, argv = ap.parse_known_args(argv)
    if args.trace_seconds is not None:
        serve.TRACE_SECONDS = args.trace_seconds
    seen: dict = {}

    class Run(serve.Run):
        def free(self):
            phases.window_records(self)      # before the engine goes
            seen["run"] = self
            super().free()

    load = trace_lib.load

    def load_and_keep(path):
        seen["trace"] = tr = load(path)
        seen["spans"] = phases.load_spans(path)
        seen["gaps"] = long_gaps(path, tr)
        return tr

    serve.Run, trace_lib.load = Run, load_and_keep
    rc = bench_run.main(argv)
    if "run" in seen:
        out = report(seen["run"], seen.get("trace"), seen.get("spans"))
        if "gaps" in seen:
            out["idle"]["long_gaps"] = seen["gaps"]
        print("PHASES", json.dumps(out), file=sys.stderr)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
