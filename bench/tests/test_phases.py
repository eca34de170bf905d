"""The engine-span reduction (``bench/lib/phases.py``) and the
``host_step_ms`` reader: on hand-made spans, events and records, and on
the small trace recorded on one TPU v5e (kept in ``data/``)."""
import os
import types

import numpy as np
import pytest

from bench.lib import phases as P
from bench.lib import trace as T
from bench.metrics import host_step_ms

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "data", "small.xplane.pb")


def ev(name, s, e):
    return T.Event(name, s, e)


def test_innermost_pieces_of_nested_spans():
    spans = [ev("bench.step", 0, 100), ev("engine.step", 5, 95),
             ev("engine.decode.prepare", 10, 30),
             ev("engine.gc", 20, 25), ev("engine.decode.wait", 40, 90),
             ev("bench.outputs", 100, 110)]
    got = [(s, e, spans[i].name) for s, e, i in P.innermost(spans)]
    assert got == [(0, 5, "bench.step"), (5, 10, "engine.step"),
                   (10, 20, "engine.decode.prepare"), (20, 25, "engine.gc"),
                   (25, 30, "engine.decode.prepare"), (30, 40, "engine.step"),
                   (40, 90, "engine.decode.wait"), (90, 95, "engine.step"),
                   (95, 100, "bench.step"), (100, 110, "bench.outputs")]


def test_gaps_go_to_the_innermost_span_over_most_of_them():
    spans = [ev("bench.window", 0, 200), ev("bench.step", 0, 100),
             ev("engine.step", 5, 95), ev("engine.decode.prepare", 10, 30),
             ev("engine.gc", 20, 25), ev("engine.decode.commit", 60, 90),
             ev("bench.outputs", 100, 110)]
    # (8, 28): prepare 13 ns, gc 5, engine.step 2 -> prepare
    # (50, 105): commit 30, engine.step 15, bench.step 5, outputs 5
    # (95, 130): bench.step 5, outputs 10, nothing 20 -> outputs
    got = P.attribute([(8, 28), (50, 105), (95, 130), (300, 310)], spans)
    assert got == pytest.approx({"engine.decode.prepare": 20e-9,
                                 "engine.decode.commit": 55e-9,
                                 "bench.outputs": 35e-9,
                                 "unattributed": 10e-9})
    # moved 40 ns later, the spans leave (8, 28) uncovered
    moved = P.attribute([(8, 28)], spans, shift=40)
    assert moved == pytest.approx({"unattributed": 20e-9})


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_on_spans_that_do_not_nest_it_is_the_benchmarks_attribution(seed):
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, 10_000), 60, replace=False))
    names = ["bench.step", "bench.outputs", "bench.add_request"]
    spans = [ev("bench.window", 0, 10_000)] + [
        ev(names[i % 3], int(a), int(b))
        for i, (a, b) in enumerate(zip(cuts[::2], cuts[1::2]))]
    starts = np.sort(rng.choice(np.arange(0, 10_000), 40, replace=False))
    gaps = [(int(s), int(s + rng.integers(1, 400))) for s in starts]
    assert P.attribute(gaps, spans) == pytest.approx(T.attribute(gaps, spans))
    # test_trace.py's hand-made case
    spans = [ev("bench.window", 0, 100), ev("bench.step", 0, 40),
             ev("bench.outputs", 40, 45), ev("bench.generator", 45, 100)]
    gaps = [(0, 10), (30, 50), (60, 95), (200, 210)]
    assert P.attribute(gaps, spans) == pytest.approx(T.attribute(gaps, spans))


def test_host_offset_recovers_a_known_shift():
    shift = -1_200_000                    # the device's clock reads earlier
    spans, mods = [], []
    for k in range(20):
        t = 10_000_000 + k * 70_000_000
        launch, run_ns, back = 40_000 + 3_000 * (k % 5), 60_000_000, 90_000
        spans += [ev("engine.step", t, t + 69_000_000),
                  ev("engine.decode.prepare", t + 1_000, t + 5_000_000),
                  ev("engine.decode.dispatch", t + 5_000_000, t + 5_300_000),
                  ev("engine.decode.wait", t + 5_300_000,
                     t + 5_000_000 + launch + run_ns + back)]
        s = t + 5_000_000 + launch + shift
        mods.append(ev("jit__step_impl(7)", s, s + run_ns))
        # a prefill program between steps takes no part
        mods.append(ev("jit__chunk_impl(3)", s + run_ns + 10, s + run_ns + 99))
    got, lo, hi = P.host_offset(spans, mods)
    # bounds: the least launch delay and the least read-back
    assert lo == shift - 90_000 and hi == shift + 40_000
    assert lo <= got <= hi and abs(got - shift) <= 65_000
    assert P.host_offset([s for s in spans if "dispatch" not in s.name],
                         mods) is None


def test_recorded_trace_reads_as_before():
    """The small trace holds only the benchmark's spans: the summary reads
    what it read before the engine had spans, and the engine-span
    attribution gives the same split with no offset."""
    s = T.summarize(T.load(SMALL))
    assert s.busy_s == pytest.approx(8.5722e-05, rel=1e-12)
    assert s.window_s == pytest.approx(0.012040339, rel=1e-12)
    assert s.idle_by_span == pytest.approx(
        {"bench.generator": 0.011954616999999999})
    assert s.top_ops == [["paged_decode_attention.1", 5.1337e-05],
                         ["convolution_tanh_fusion.2", 1.3142e-05],
                         ["convolution_tanh_fusion", 1.2703e-05],
                         ["copy-done", 7.110999999999999e-06],
                         ["copy_bitcast_fusion", 7.179999999999999e-07],
                         ["copy.1", 5.1e-07], ["reshape.2", 8.4e-08],
                         ["reshape.3", 7.2e-08], ["copy-start", 3.1e-08],
                         ["copy-start.1", 8e-09]]
    tr = T.load(SMALL)
    spans = P.load_spans(SMALL)
    assert [e.name for e in spans] == [e.name for e in tr.spans]
    assert P.host_offset(spans, tr.modules["/device:TPU:0"]) is None
    gaps = T.idle_gaps(tr.ops["/device:TPU:0"], s.lo, s.hi)
    assert P.attribute(gaps, spans) == pytest.approx(s.idle_by_span)


class Rec:
    """A hand-made step record, as the engine keeps them."""

    def __init__(self, t0_s, ms, wait_ms):
        self.t0_ns = int(t0_s * 1e9)
        self.t1_ns = self.t0_ns + int(ms * 1e6)
        w = int(wait_ms * 1e6)
        self.phase_ns = {"engine.step": 100, "engine.decode.wait": w,
                         "engine.prefill.wait": 0,
                         "engine.decode.prepare": self.t1_ns - self.t0_ns
                         - w - 100}


def test_host_step_ms_reads_the_untraced_steps_median():
    recs = [Rec(0.5, 70.0, 66.0),                     # set-up
            Rec(10.0, 70.0, 60.0), Rec(10.1, 80.0, 60.0),   # traced
            Rec(20.0, 66.0, 60.0), Rec(20.1, 5000.0, 60.0),  # untraced
            Rec(25.2, 71.0, 66.0),
            Rec(60.0, 70.0, 1.0)]                     # after the window
    run = types.SimpleNamespace(traced=(10.0, 15.0), window=(10.0, 51.0),
                                llm=types.SimpleNamespace(
                                    step_log=lambda: list(recs)))
    assert host_step_ms.read(types.SimpleNamespace(run=run)) == \
        pytest.approx(6.0)
    assert P.host_ns(recs[4]) == int(4940e6)
    # the engine is drained once; later reads see what the first kept
    assert host_step_ms.read(types.SimpleNamespace(run=run)) == \
        pytest.approx(6.0)
    # an engine that keeps no records, and an untraced run, give nothing
    bare = types.SimpleNamespace(traced=(10.0, 15.0), window=(10.0, 51.0),
                                 llm=object())
    assert host_step_ms.read(types.SimpleNamespace(run=bare)) is None
    untraced = types.SimpleNamespace(traced=None, window=(10.0, 51.0),
                                     llm=run.llm)
    assert host_step_ms.read(types.SimpleNamespace(run=untraced)) is None
