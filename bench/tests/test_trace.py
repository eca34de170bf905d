"""The trace reduction: on hand-made events, and on a small trace recorded
on one TPU v5e by ``record_trace.py`` (kept in ``data/``)."""
import os

import pytest

from bench.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "data", "small.xplane.pb")


def ev(name, s, e):
    return T.Event(name, s, e)


def test_busy_union_and_gaps_by_hand():
    ops = [ev("a", 10, 20), ev("b", 15, 30), ev("c", 50, 60), ev("d", 95, 120)]
    assert T.merge([(e.start, e.end) for e in ops], 0, 100) == [
        (10, 30), (50, 60), (95, 100)]
    assert T.busy_ns(ops, 0, 100) == 20 + 10 + 5
    assert T.idle_gaps(ops, 0, 100) == [(0, 10), (30, 50), (60, 95)]


def test_gaps_go_to_the_span_that_covers_them_most():
    spans = [ev("bench.window", 0, 100), ev("bench.step", 0, 40),
             ev("bench.outputs", 40, 45), ev("bench.generator", 45, 100)]
    gaps = [(0, 10), (30, 50), (60, 95)]
    got = T.attribute(gaps, spans)
    # (30, 50): 10 ns in step, 5 in outputs, 5 in generator -> step
    assert got == pytest.approx({"bench.step": 30e-9,
                                 "bench.generator": 35e-9})
    assert T.attribute([(200, 210)], spans) == pytest.approx(
        {"unattributed": 10e-9})


def test_matching_counts_events_by_name_inside_the_window():
    mods = [ev("jit__step_impl(1)", 0, 10), ev("jit__chunk_impl(2)", 10, 15),
            ev("jit__step_impl(1)", 20, 26), ev("jit__step_impl(1)", 200, 210)]
    assert T.matching(mods, "jit__step_impl", 0, 100) == pytest.approx(
        (16e-9, 2))
    (name, secs), = T.top_ops(mods, 0, 100, k=1)
    assert name == "jit__step_impl(1)" and secs == pytest.approx(16e-9)


def test_top_ops_charge_a_loop_only_its_own_time():
    ops = [ev("%while.5 = (s32[]) while(...)", 0, 100),
           ev("%fusion.1 = bf16[16] fusion(...)", 10, 30),
           ev("%paged_decode_attention.9 = bf16[16] custom-call(...)", 30, 90),
           ev("%fusion.1 = bf16[16] fusion(...)", 120, 130)]
    got = T.top_ops(ops, 0, 200)
    assert [n for n, _ in got] == ["paged_decode_attention.9", "fusion.1",
                                   "while.5"]
    assert [s for _, s in got] == pytest.approx([60e-9, 30e-9, 20e-9])


def test_recorded_tpu_trace():
    tr = T.load(SMALL)
    assert list(tr.ops) == ["/device:TPU:0"]
    s = T.summarize(tr)
    # three steps, each a jitted matmul chain and the paged kernel, with a
    # 2 ms host sleep in a generator span after each
    assert 0 < s.busy_s < s.window_s
    assert s.idle_by_span.get("bench.generator", 0) >= 3 * 0.002 * 0.9
    everything = (0, 2**62)
    secs, n = T.matching(s.ops, r"^paged_decode_attention", *everything)
    assert n == 3 and secs > 0
    # operands do not count: the reshape that reads the kernel's output
    # names it in its HLO text, and is not the kernel
    assert any("paged_decode_attention" in e.name and not
               T.short(e.name).startswith("paged") for e in s.ops)
    assert T.matching(s.modules, "jit_step", *everything)[1] == 3
    assert s.top_ops[0][0].startswith("paged_decode_attention")
