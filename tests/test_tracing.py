"""Host spans and step records (``runtime.tracing``): how spans nest and
what a record adds up to, the log's bound, full collections inside a step,
and the records the continuous engine keeps of every ``step()``."""
import dataclasses
import gc

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.model import build_model
from repro.runtime import tracing
from repro.runtime.engine import ContinuousServeEngine, DisaggServeEngine
from repro.runtime.llm import LLMEngine
from repro.runtime.sampling import SamplingParams
from repro.runtime.scheduler import Request


def _busy(ns: int) -> None:
    t = tracing._clock()
    while tracing._clock() - t < ns:
        pass


def test_spans_nest_and_self_time_excludes_children():
    log = tracing.StepLog()
    with log.step() as rec:
        with tracing.span("engine.decode.prepare") as outer:
            _busy(200_000)
            with tracing.span("engine.decode.wait") as inner:
                _busy(300_000)
            with tracing.span("engine.decode.wait") as again:
                _busy(100_000)
    (got,) = log.drain()
    assert got is rec
    ph = rec.phase_ns
    assert ph["engine.decode.wait"] == inner.ns + again.ns
    assert ph["engine.decode.prepare"] == outer.ns - inner.ns - again.ns
    assert ph["engine.decode.prepare"] >= 200_000
    # the phases, engine.step's own time among them, add up to the step
    assert sum(ph.values()) == rec.t1_ns - rec.t0_ns == rec.ns
    assert ph["engine.step"] >= 0


def test_span_outside_a_step_records_nothing():
    with tracing.span("engine.admit") as s:
        pass
    assert s.ns >= 0 and tracing._open.record is None


def test_step_log_is_bounded_and_drain_empties_it():
    log = tracing.StepLog()
    n = tracing.MAX_RECORDS + 10
    for _ in range(n):
        with log.step():
            pass
    out = log.drain()
    assert len(out) == tracing.MAX_RECORDS
    assert all(a.t0_ns <= b.t0_ns for a, b in zip(out, out[1:]))
    assert log.drain() == []


def test_full_collection_inside_a_step_is_an_engine_gc_span():
    log = tracing.StepLog()
    with log.step() as rec:
        with tracing.span("engine.decode.commit"):
            gc.collect()
    assert rec.gc_ns > 0
    assert rec.phase_ns["engine.gc"] == rec.gc_ns
    assert sum(rec.phase_ns.values()) == rec.ns
    # a young-generation collection is not timed, nor one outside a step
    with log.step() as young:
        gc.collect(0)
    assert young.gc_ns == 0 and "engine.gc" not in young.phase_ns
    gc.collect()
    assert tracing._open.gc_span is None


@pytest.fixture(scope="module")
def small():
    cfg = reduced_config(get_config("qwen3-14b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n=5, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               int(rng.integers(3, 14))
                                               ).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 9)))
            for i in range(n)]


def test_engine_records_match_what_each_step_returned(small):
    cfg, model, params = small
    eng = ContinuousServeEngine(model, params, num_slots=3, page_size=4,
                                num_pages=40, max_len=32, prefill_chunk=5)
    reqs = _requests(cfg)
    for r in reqs:
        eng.add_request(r)
    emitted, seen = [], set()
    while eng.has_unfinished():
        outs = eng.step()
        # a request's first token comes from its last prefill chunk; every
        # later one from a decode step
        decoded = 0
        for o in outs:
            decoded += len(o.new_token_ids) - (o.rid not in seen
                                               and bool(o.new_token_ids))
            seen |= {o.rid} if o.new_token_ids else set()
        emitted.append((decoded, sum(o.finished for o in outs)))
    recs = eng.step_log()
    assert len(recs) == len(emitted) and eng.step_log() == []
    assert any(r.prefill_rows and r.decode_slots for r in recs)
    for rec, (decoded, finished) in zip(recs, emitted):
        assert rec.decode_slots == decoded
        assert rec.finished == finished
        assert rec.t0_ns <= rec.t1_ns
        assert sum(rec.phase_ns.values()) == rec.ns
        assert set(rec.phase_ns) <= {
            "engine.step", "engine.admit", "engine.gc",
            *(f"engine.{a}.{b}" for a in ("prefill", "decode")
              for b in ("prepare", "dispatch", "wait", "commit"))}
        assert rec.pages_live >= 0
    assert sum(r.admitted for r in recs) == len(reqs)
    assert sum(r.finished for r in recs) == len(reqs)
    assert sum(r.prefill_tokens for r in recs) == sum(
        r.prompt_len for r in reqs)
    assert recs[-1].pages_live == eng.cache.allocator.num_live


def test_run_stats_come_from_the_records(small):
    cfg, model, params = small

    def engine():
        return ContinuousServeEngine(model, params, num_slots=2, page_size=4,
                                     num_pages=12, max_len=28,
                                     prefill_chunk=5,
                                     enable_prefix_cache=True)

    eng = engine()
    stats = eng.run(_requests(cfg, n=6, seed=7))
    per = stats.per_request.values()
    assert stats.chunks == sum(r["chunks"] for r in per)
    # the same requests stepped by hand: the records add up to the stats
    other = engine()
    for r in _requests(cfg, n=6, seed=7):
        other.add_request(r)
    while other.has_unfinished():
        other.step()
    recs = other.step_log()
    slots = [r.decode_slots for r in recs if r.decode_slots]
    assert stats.steps == len(slots)
    assert stats.occupancy == pytest.approx(sum(slots) / 2 / len(slots))
    assert stats.chunks == sum(r.prefill_rows for r in recs)
    assert stats.prefill_tokens == sum(r.prefill_tokens for r in recs)
    assert stats.preemptions == sum(r.preempted for r in recs)
    assert stats.steps > 0 and 0 < stats.occupancy <= 1.0
    assert stats.prefill_tokens <= stats.prompt_tokens
    assert stats.host_ms["engine.step"] > 0
    assert {"engine.decode.dispatch", "engine.decode.wait",
            "engine.prefill.dispatch"} <= set(stats.host_ms)
    # run() keeps the records it counted, so the log is left empty
    assert eng.step_log() == []


def test_llm_and_disagg_step_logs(small):
    cfg, model, params = small
    llm = LLMEngine(model, params, backend="continuous", max_len=32,
                    num_slots=2, page_size=4, prefill_chunk=5)
    llm.add_request(np.arange(1, 9, dtype=np.int32),
                    SamplingParams(max_tokens=3))
    steps = 0
    while llm.has_unfinished():
        llm.step()
        steps += 1
    assert len(llm.step_log()) == steps
    with pytest.raises(ValueError):
        LLMEngine(model, params, backend="static", max_len=32).step_log()

    eng = DisaggServeEngine(model, params, num_slots=2, page_size=4,
                            num_pages=24, max_len=32, prefill_chunk=5)
    reqs = _requests(cfg, n=3, seed=11)
    stats = eng.run(reqs)
    assert stats.prefill_tokens == sum(r.prompt_len for r in reqs)
    assert stats.steps > 0 and stats.host_ms
    for r in reqs:
        eng.add_request(Request(rid=10 + r.rid, prompt=r.prompt,
                                max_new_tokens=r.max_new_tokens))
    while eng.has_unfinished():
        eng.step()
    recs = eng.step_log()
    assert all(a.t0_ns <= b.t0_ns for a, b in zip(recs, recs[1:]))
    assert sum(r.prefill_tokens for r in recs) <= sum(
        r.prompt_len for r in reqs)
    assert sum(r.decode_slots for r in recs) > 0


@pytest.mark.parametrize("arch,head_dim", [
    ("qwen3-14b", None), ("h2o-danube-1.8b", None),
    # 2 KV heads x 64: a width of one 128-lane tile, so the chunk walk
    ("qwen3-14b", 64), ("h2o-danube-1.8b", 64),
], ids=["qwen3-14b", "h2o-danube-1.8b", "qwen3-14b-chunked",
        "h2o-danube-1.8b-chunked"])
def test_decode_records_count_the_kernels_live_and_walked_pages(arch,
                                                                head_dim):
    """``kv_pages_live``: one layer's pages holding a position each
    decoding slot's new token sees, counted here block by block from the
    positions and tables the decode step was handed; ``kv_pages_walked``
    rounds each slot's up to whole chunks (one page on the page walk)."""
    cfg = reduced_config(get_config(arch))
    if head_dim is not None:
        cfg = dataclasses.replace(cfg, head_dim=head_dim)
        assert cfg.n_kv_heads * head_dim == 128
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    page, window = 4, cfg.sliding_window
    eng = ContinuousServeEngine(model, params, num_slots=3, page_size=page,
                                num_pages=40, max_len=32, prefill_chunk=5)
    calls, step_fn = [], eng._step_fn

    def spy(*args):       # params, pools, states, presence, tokens, pos, ...
        pos, table, ring = (np.asarray(a) if a is not None else None
                            for a in args[5:8])
        calls.append((pos, table if ring is None else ring))
        return step_fn(*args)

    eng._step_fn = spy
    for r in _requests(cfg, n=5, seed=5):
        r.max_new_tokens += 12                   # decode past the window
        eng.add_request(r)
    (ppb,) = eng._kv_walks.values()
    assert (ppb > 1) == (head_dim is not None)
    while eng.has_unfinished():
        eng.step()
    recs = [r for r in eng.step_log() if r.decode_slots]
    assert len(recs) == len(calls) > 0
    for rec, (pos, table) in zip(recs, calls):
        live = 0
        for slot, p in enumerate(pos):
            if table[slot, p // page] == 0:      # not decoding this step
                continue
            live += sum(1 for j in range(p // page + 1)
                        if window is None or j * page + page - 1 > p - window)
        assert rec.kv_pages_live == live
        assert live <= rec.kv_pages_walked < live + rec.decode_slots * ppb
        assert rec.kv_pages_walked % ppb == 0
    if window is not None:
        assert max(r.kv_pages_live / r.decode_slots for r in recs) <= (
            -(-window // page) + 1)
