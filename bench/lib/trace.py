"""Reduction of a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it with
nothing but JAX.  Device planes are named ``/device:TPU:<n>``; on each, the
``XLA Modules`` line holds one event per execution of a compiled program
(named after the jitted function, e.g. ``jit__step_impl(...)``) and the
``XLA Ops`` line one event per operation (fusions, copies, and Pallas
kernels as custom calls).  Host planes hold the benchmark's own
``TraceAnnotation`` spans (``bench.*``) on the threads that opened them.
All events carry start times in nanoseconds on one timeline; on a v5e the
device's events land about 1.1-1.3 ms before the host spans that caused
them (the recorded probe in ``bench/tests/data``), which the reduction
does not correct: it moves a 10 s window's edges by one step at most, and
can hand an idle gap shorter than that to the span before it.

What is computed here, and nowhere else:

* the busy union of a device's operations inside a window, and the idle
  gaps between them;
* each idle gap attributed to the benchmark span that overlaps it most
  (what the host was doing while the device waited);
* device time and launch count of events whose name matches a pattern.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
MODULES, OPS = "XLA Modules", "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: int        # ns
    end: int          # ns


@dataclasses.dataclass
class Trace:
    """The events the reduction uses, per device and for the host."""
    modules: dict      # device plane name -> [Event]
    ops: dict          # device plane name -> [Event]
    spans: list        # [Event] of host spans named bench.*

    def window(self, name: str = "bench.window") -> tuple[int, int] | None:
        """(start, end) of the first host span called ``name``."""
        for ev in self.spans:
            if ev.name == name:
                return ev.start, ev.end
        return None


def _events(line) -> list[Event]:
    return [Event(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or the newest one under a directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    modules, ops, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            for line in plane.lines:
                if line.name == MODULES:
                    modules[plane.name] = _events(line)
                elif line.name == OPS:
                    ops[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name.startswith(SPAN_PREFIX)]
    spans.sort(key=lambda e: e.start)
    return Trace(modules=modules, ops=ops, spans=spans)


def merge(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events, lo: int, hi: int) -> int:
    return sum(e - s for s, e in merge(((ev.start, ev.end) for ev in events),
                                       lo, hi))


def idle_gaps(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of ``[lo, hi]`` in which no event runs."""
    gaps, t = [], lo
    for s, e in merge(((ev.start, ev.end) for ev in events), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute(gaps, spans, outer: str = "bench.window") -> dict:
    """Seconds of idle device time per benchmark span: each gap goes to
    the span (other than ``outer``) that overlaps it most, or to
    ``unattributed``."""
    inner = sorted((s for s in spans if s.name != outer),
                   key=lambda s: s.start)
    starts = [s.start for s in inner]
    out: collections.Counter = collections.Counter()
    for gs, ge in gaps:
        best, best_ov = "unattributed", 0
        # the benchmark's spans follow one another on one thread, so the
        # candidates are the spans that start before the gap ends, back to
        # the first that ended before it began
        i = bisect.bisect_left(starts, ge) - 1
        while i >= 0 and inner[i].end > gs:
            sp = inner[i]
            ov = min(ge, sp.end) - max(gs, sp.start)
            if ov > best_ov:
                best, best_ov = sp.name, ov
            i -= 1
        out[best] += (ge - gs) / 1e9
    return dict(out)


def short(name: str) -> str:
    """An operation's name without its HLO text (``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def matching(events, pattern: str, lo: int, hi: int) -> tuple[float, int]:
    """(seconds, count) of events whose short name (an operation's own
    name, not the operands in its HLO text) matches ``pattern`` and that
    start inside ``[lo, hi]``."""
    rx = re.compile(pattern)
    secs, n = 0.0, 0
    for ev in events:
        if lo <= ev.start <= hi and rx.search(short(ev.name)):
            secs += (ev.end - ev.start) / 1e9
            n += 1
    return secs, n


def self_times(events, lo: int, hi: int) -> collections.Counter:
    """Device seconds per operation name in the window, each operation's
    own time only: a loop or call that contains other operations (the
    layer scan's ``while``) is charged what its contents do not cover."""
    c: collections.Counter = collections.Counter()
    stack: list[list] = []                  # [event, own ns]
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        if not lo <= ev.start <= hi:
            continue
        while stack and stack[-1][0].end <= ev.start:
            done, own = stack.pop()
            c[short(done.name)] += own / 1e9
        dur = ev.end - ev.start
        if stack:
            stack[-1][1] -= min(ev.end, stack[-1][0].end) - ev.start
        stack.append([ev, dur])
    for done, own in stack:
        c[short(done.name)] += own / 1e9
    return c


def top_ops(events, lo: int, hi: int, k: int = 10) -> list:
    """The ``k`` operations with the most own device time in the window."""
    return [[name, secs]
            for name, secs in self_times(events, lo, hi).most_common(k)]


@dataclasses.dataclass
class Summary:
    """One traced window, reduced: what metrics and the result line read."""
    lo: int
    hi: int
    busy_s: float           # mean over the devices used
    window_s: float
    ops: list               # [Event] of every device used
    modules: list
    idle_by_span: dict
    top_ops: list

    def seconds(self, pattern: str, which: str = "ops") -> tuple[float, int]:
        return matching(self.ops if which == "ops" else self.modules,
                        pattern, self.lo, self.hi)


def summarize(tr: Trace) -> Summary:
    """Reduce a trace to its window (the ``bench.window`` span)."""
    win = tr.window()
    if win is None:
        raise ValueError("the trace has no bench.window span")
    lo, hi = win
    devices = sorted(tr.ops)
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    busy = [busy_ns(tr.ops[d], lo, hi) / 1e9 for d in devices]
    ops = [ev for d in devices for ev in tr.ops[d]]
    mods = [ev for d in devices for ev in tr.modules.get(d, [])]
    gaps = idle_gaps(tr.ops[devices[0]], lo, hi)
    return Summary(lo=lo, hi=hi, busy_s=sum(busy) / len(busy),
                   window_s=(hi - lo) / 1e9, ops=ops, modules=mods,
                   idle_by_span=attribute(gaps, tr.spans),
                   top_ops=top_ops(ops, lo, hi))
