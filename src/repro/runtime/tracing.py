"""Host spans and per-step counters of the serving engine.

Every ``ContinuousServeEngine.step`` call opens one :class:`StepRecord` and
a tree of named host spans inside it (``engine.step`` around the whole
call; ``engine.admit``, ``engine.prefill.*``, ``engine.decode.*`` for its
phases; ``engine.gc`` for a full garbage collection that lands inside
it).  Each span does two things:

* while the profiler records, it enters
  ``jax.profiler.TraceAnnotation(name)``, so that the span sits on the
  same timeline as the device's events;
* it adds its self time (its duration less that of the spans nested in
  it), on ``time.perf_counter_ns``, to the open step's record, so that a
  record's phases add up to the step's duration exactly.

The record also holds what the step did: decode slots run, prefill rows
and prompt tokens computed, requests admitted, finished and preempted,
copy-on-write page copies, pages live after it, the K/V (or latent)
pages the decode kernel read and walked, and the expert share's rows and
active experts.  Records go to a
bounded :class:`StepLog` (``MAX_RECORDS``, the oldest dropped first) that
``step_log()`` on the engines drains.  All of it is always on, with no
switch: a step's spans cost some 10 us of host time.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import threading
import time

import jax

MAX_RECORDS = 65_536

_clock = time.perf_counter_ns
_Annotation = jax.profiler.TraceAnnotation
_tracing = _Annotation.is_enabled        # is the profiler recording?


@dataclasses.dataclass(slots=True)
class StepRecord:
    """What one ``step()`` call did, and where its host time went."""
    t0_ns: int                   # perf_counter_ns at entry
    t1_ns: int = 0               # perf_counter_ns at return
    phase_ns: dict = dataclasses.field(default_factory=dict)
    # span name -> self ns; the values add up to t1_ns - t0_ns
    gc_ns: int = 0               # full collections inside the step
    decode_slots: int = 0        # slots the decode step (or window) ran
    prefill_rows: int = 0        # requests advanced by a prefill chunk
    prefill_tokens: int = 0      # prompt tokens computed
    admitted: int = 0
    finished: int = 0
    preempted: int = 0
    cow_copies: int = 0          # pages copied by the copy-on-write barrier
    pages_live: int = 0          # live pool pages after the step
    # the paged decode kernel's walk, one layer of each window, summed over
    # the decoding slots: pages holding a position the new token sees, and
    # those pages rounded up to whole chunks
    kv_pages_live: int = 0
    kv_pages_walked: int = 0
    # a held expert share's decode step, summed over its MoE layers: the
    # decoding slots' rows routed to held experts, and the held experts
    # that took at least one of them
    moe_rows_held: int = 0
    moe_experts_active: int = 0

    @property
    def ns(self) -> int:
        return self.t1_ns - self.t0_ns


class _Open(threading.local):
    record: StepRecord | None = None     # the step open on this thread
    span: "span | None" = None           # its innermost open span
    gc_span: "span | None" = None


_open = _Open()


class span:
    """``with span("engine.decode.wait"): ...`` -- a named host span: a
    profiler annotation while the profiler records, and self time added to
    the open step's record (outside a step, only the annotation)."""
    __slots__ = ("name", "ns", "_ann", "_t0", "_child", "_parent", "_on")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        if _tracing():
            self._ann = ann = _Annotation(self.name)
            ann.__enter__()
        else:
            self._ann = None
        self._on = on = _open
        self._parent = on.span
        on.span = self
        self._child = 0
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.ns = ns = _clock() - self._t0
        on = self._on
        parent = on.span = self._parent
        if parent is not None:
            parent._child += ns
        rec = on.record
        if rec is not None:
            ph = rec.phase_ns
            ph[self.name] = ph.get(self.name, 0) + ns - self._child
        if self._ann is not None:
            self._ann.__exit__(*exc)


class _Step(span):
    """The ``engine.step`` span: opens a record, and logs it on exit."""
    __slots__ = ("rec", "_log", "_prev")

    def __init__(self, log: "StepLog"):
        super().__init__("engine.step")
        self._log = log

    def __enter__(self) -> StepRecord:
        self._prev = _open.record
        super().__enter__()
        self.rec = _open.record = StepRecord(t0_ns=self._t0)
        return self.rec

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        self.rec.t1_ns = self._t0 + self.ns
        _open.record = self._prev
        self._log._records.append(self.rec)


class StepLog:
    """The engine's bounded record of its ``step()`` calls."""

    def __init__(self):
        self._records: collections.deque = collections.deque(
            maxlen=MAX_RECORDS)

    def step(self) -> _Step:
        """``with log.step() as rec:`` around one ``step()`` call."""
        return _Step(self)

    def drain(self) -> list[StepRecord]:
        """The records logged so far, oldest first; the log is left empty."""
        out = list(self._records)
        self._records.clear()
        return out


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: a full (generation 2) collection inside a
    step becomes an ``engine.gc`` span and adds to the step's ``gc_ns``."""
    if info["generation"] != 2:
        return
    if phase == "start":
        if _open.record is not None:
            _open.gc_span = span("engine.gc").__enter__()
        return
    s, _open.gc_span = _open.gc_span, None
    if s is not None:
        s.__exit__(None, None, None)
        if _open.record is not None:
            _open.record.gc_ns += s.ns


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)


def phase_ms(records) -> dict[str, float]:
    """Host milliseconds by span name, summed over ``records``."""
    out: collections.Counter = collections.Counter()
    for r in records:
        for name, ns in r.phase_ns.items():
            out[name] += ns / 1e6
    return dict(out)
