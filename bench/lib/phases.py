"""The serving engine's own spans and step records, read beside the trace.

The engine opens a ``engine.step`` span around every ``step()`` call and
one span per phase inside it (``engine.admit``, ``engine.prefill.*``,
``engine.decode.*``, ``engine.gc``), and keeps a ``StepRecord`` of each
call: host self time by phase and what the step did.  This module reads
both:

* ``load_spans``: the ``bench.*`` and ``engine.*`` host spans of a trace;
* ``attribute``: each idle gap of the device to the innermost span over
  most of it (on spans that do not nest, the span that overlaps it most:
  what ``trace.attribute`` computes);
* ``host_offset``: the shift that places host spans on the device's
  clock, measured from each decode step's program and the engine spans
  that dispatched it and read its results back;
* ``window_records`` and ``host_ns``: the engine's records of a run, and a
  step's host self time.

An engine that keeps no records, or a trace with no engine spans, gives
empty records and no offset; ``attribute`` then reads as ``trace.attribute``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

from bench.lib import trace as trace_lib

SPAN_PREFIXES = ("bench.", "engine.")
# a decode step's program, and the engine spans that launch it and read
# its results back
STEP_PROGRAM = r"^jit__step_impl\b"
DISPATCH, WAIT = "engine.decode.dispatch", "engine.decode.wait"


def trace_file(path: str) -> str:
    """``path``, or the newest ``.xplane.pb`` under it if a directory."""
    if not os.path.isdir(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load_spans(path: str) -> list:
    """The ``bench.*`` and ``engine.*`` host spans of an ``.xplane.pb``
    file, or of the newest one under a directory, by start."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(trace_file(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in trace_lib._events(line)
                          if e.name.startswith(SPAN_PREFIXES)]
    spans.sort(key=lambda e: e.start)
    return spans


def innermost(spans) -> list[tuple[int, int, int]]:
    """Disjoint ``(start, end, i)`` pieces of the time the spans cover, in
    order: ``i`` indexes the innermost span over the piece (of spans that
    nest, the one opened last)."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].start, -spans[i].end))
    pieces, stack, t = [], [], None

    def upto(x):
        nonlocal t
        if stack and x > t:
            pieces.append((t, x, stack[-1]))
        t = x if t is None else max(t, x)

    for i in order:
        sp = spans[i]
        while stack and spans[stack[-1]].end <= sp.start:
            upto(spans[stack[-1]].end)
            stack.pop()
        upto(sp.start)
        stack.append(i)
    while stack:
        upto(spans[stack[-1]].end)
        stack.pop()
    return pieces


def attribute(gaps, spans, outer: str = "bench.window",
              shift: int = 0) -> dict:
    """Seconds of idle device time per span: each nanosecond of a gap
    belongs to the innermost span over it (other than ``outer``), and the
    gap goes to the span that owns most of it, or to ``unattributed``.
    ``shift`` (ns) is added to every span to place it on the device's
    clock."""
    inner = [trace_lib.Event(s.name, s.start + shift, s.end + shift)
             for s in spans if s.name != outer]
    pieces = innermost(inner)
    starts = [p[0] for p in pieces]
    out: collections.Counter = collections.Counter()
    for gs, ge in gaps:
        owned: collections.Counter = collections.Counter()
        j = max(bisect.bisect_right(starts, gs) - 1, 0)
        while j < len(pieces) and pieces[j][0] < ge:
            ps, pe, i = pieces[j]
            if min(ge, pe) > max(gs, ps):
                owned[i] += min(ge, pe) - max(gs, ps)
            j += 1
        # most nanoseconds; of equals, the span that began last
        best = max(owned, key=lambda i: (owned[i], inner[i].start),
                   default=None)
        name = "unattributed" if best is None else inner[best].name
        out[name] += (ge - gs) / 1e9
    return dict(out)


def host_offset(spans, modules) -> tuple[int, int, int] | None:
    """``(shift, lo, hi)`` in ns: what to add to a host span to place it on
    the device's clock.  A decode step's program starts no earlier than
    the ``DISPATCH`` span that launched it and ends no later than the
    first ``WAIT`` span after that, so each such pair bounds the shift:
    ``lo`` is the largest lower bound, ``hi`` the least upper one, and
    ``shift`` their midpoint.  None when the trace holds no such pair."""
    rx = re.compile(STEP_PROGRAM)
    progs = sorted((m for m in modules if rx.search(trace_lib.short(m.name))),
                   key=lambda m: m.start)
    waits = sorted((s for s in spans if s.name == WAIT),
                   key=lambda s: s.start)
    pstarts, wstarts = [m.start for m in progs], [w.start for w in waits]
    lo = hi = None
    for d in (s for s in spans if s.name == DISPATCH):
        w = bisect.bisect_left(wstarts, d.end)
        k = bisect.bisect_left(pstarts, d.start)
        near = progs[max(k - 1, 0):k + 1]
        if w == len(waits) or not near:
            continue
        # the program that starts nearest the dispatch: the two clocks
        # differ by far less than a step
        m = min(near, key=lambda m: abs(m.start - d.start))
        a, b = m.end - waits[w].end, m.start - d.start
        lo = a if lo is None else max(lo, a)
        hi = b if hi is None else min(hi, b)
    if lo is None:
        return None
    return (lo + hi) // 2, lo, hi


def window_records(run) -> list:
    """The engine's ``StepRecord``s of a run, drained from the engine the
    first time and kept on the run after ([] for an engine without
    ``step_log``).  Read before the run frees its engine."""
    if not hasattr(run, "engine_records"):
        log = getattr(run.llm, "step_log", None)
        run.engine_records = log() if log is not None else []
    return run.engine_records


def records_in(run, t0: float, t1: float) -> list:
    """The records of the ``step()`` calls inside ``[t0, t1]`` (seconds on
    the benchmark's clock, ``time.perf_counter``, which is the clock of
    the records' ``perf_counter_ns``)."""
    return [r for r in window_records(run)
            if t0 <= r.t0_ns / 1e9 and r.t1_ns / 1e9 <= t1]


def host_ns(record) -> int:
    """A ``step()``'s host self time: its duration less the self time of
    its ``engine.*.wait`` phases (reading results back).  The engine is
    synchronous, so for this long the chip had nothing of it queued."""
    return record.t1_ns - record.t0_ns - sum(
        ns for name, ns in record.phase_ns.items()
        if name.startswith("engine.") and name.endswith(".wait"))
