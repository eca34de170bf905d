"""Scheduler-integrated speculative decoding in the continuous engine:
greedy byte-identity vs the non-speculative engine (incl. forced
preemption restarts and prefix-cache hits), the one-compiled-window
guarantee, acceptance-rate statistics vs the analytic min(1, p/q) rule,
per-request speculation counters, prompt logprobs across backends, and
the DeploymentSpec draft/window accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.model import build_model
from repro.runtime import sampling
from repro.runtime.deployment import DeploymentSpec
from repro.runtime.engine import ContinuousServeEngine
from repro.runtime.llm import LLMEngine
from repro.runtime.sampling import SamplingParams
from repro.runtime.scheduler import Request
from repro.runtime.speculative import SpeculativeConfig

GAMMA = 3


@pytest.fixture(scope="module")
def small():
    cfg = reduced_config(get_config("qwen3-14b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def draft(small):
    """A shallower copy of the target — different weights, same vocab."""
    cfg, _, _ = small
    dcfg = dataclasses.replace(cfg, name=cfg.name + "-draft",
                               n_layers=max(1, cfg.n_layers // 2))
    dm = build_model(dcfg)
    return dm, dm.init(jax.random.PRNGKey(3))


def _reqs(toks, order, sps=None, G=8):
    return [Request(rid=i, prompt=np.asarray(toks[i]), max_new_tokens=G,
                    sampling=(sps[i] if sps else None)) for i in order]


@pytest.fixture(scope="module")
def spec_runs(small, draft):
    """Shared greedy runs: non-spec reference, self-draft spec, and
    separate-draft spec over the same four prompts."""
    cfg, model, params = small
    dm, dp = draft
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0,
                                         cfg.vocab_size))

    def engine(spec_cfg, num_pages=64):
        return ContinuousServeEngine(
            model, params, num_slots=3, page_size=4, num_pages=num_pages,
            max_len=24, prefill_chunk=5, speculative=spec_cfg)

    ref_eng = engine(None)
    ref = ref_eng.run(_reqs(toks, [0, 1, 2, 3]))
    self_eng = engine(SpeculativeConfig(gamma=GAMMA))
    self_out = self_eng.run(_reqs(toks, [0, 1, 2, 3]))
    sep_eng = engine(SpeculativeConfig(draft_model=dm, draft_params=dp,
                                       gamma=GAMMA))
    sep_out = sep_eng.run(_reqs(toks, [0, 1, 2, 3]))
    return toks, ref_eng, ref, self_eng, self_out, sep_eng, sep_out


# ---------------------------------------------------------------------------
# Greedy byte-identity (the lossless guarantee)
# ---------------------------------------------------------------------------


def test_greedy_self_draft_byte_identical(spec_runs):
    """With the target drafting for itself, every greedy proposal is the
    target argmax: full acceptance, zero waste, identical streams."""
    toks, _, ref, _, self_out, _, _ = spec_runs
    for i in range(4):
        np.testing.assert_array_equal(ref.results[i], self_out.results[i])
    assert self_out.spec_windows > 0
    assert self_out.accepted_per_window == pytest.approx(GAMMA)
    assert self_out.spec_wasted == 0


def test_greedy_separate_draft_byte_identical(spec_runs):
    """Speculative decoding never changes the target's output — a draft
    with different weights only changes how fast tokens arrive."""
    toks, _, ref, _, _, _, sep_out = spec_runs
    for i in range(4):
        np.testing.assert_array_equal(ref.results[i], sep_out.results[i])
    # drafted = gamma per window, accepted <= drafted
    assert sep_out.spec_drafted == GAMMA * sep_out.spec_windows
    assert 0 <= sep_out.spec_accepted <= sep_out.spec_drafted


def test_one_compiled_draft_and_verify_step(spec_runs):
    """The whole run — ragged admissions, retirements, a greedy batch —
    compiles exactly ONE draft scan and ONE multi-token verify step."""
    _, _, _, self_eng, _, sep_eng, _ = spec_runs
    for eng in (self_eng, sep_eng):
        assert eng._spec_draft._cache_size() == 1
        assert eng._spec_verify._cache_size() == 1


def test_greedy_identity_through_forced_preemption(small, draft, spec_runs):
    """A pool tight enough to evict mid-stream must restart gamma windows
    from the rewound position and re-emit identical greedy tokens — and
    the restart must not add compiles."""
    cfg, model, params = small
    dm, dp = draft
    toks, _, ref, _, _, _, _ = spec_runs
    tight = ContinuousServeEngine(
        model, params, num_slots=3, page_size=4, num_pages=9, max_len=24,
        prefill_chunk=5,
        speculative=SpeculativeConfig(draft_model=dm, draft_params=dp,
                                      gamma=GAMMA))
    out = tight.run(_reqs(toks, [0, 1, 2, 3]))
    assert out.preemptions > 0
    for i in range(4):
        np.testing.assert_array_equal(ref.results[i], out.results[i])
    assert tight._spec_draft._cache_size() == 1
    assert tight._spec_verify._cache_size() == 1


def test_greedy_identity_with_prefix_cache_hits(spec_runs):
    """Admission through shared prefix pages (skipped prefill) lands in
    the same speculative stream."""
    toks, _, ref, _, _, sep_eng, _ = spec_runs
    # the second request arrives once the first has finished its prefill
    # and registered its pages, however slow the host is: arriving during
    # that prefill it would find nothing to share
    out = sep_eng.run([Request(rid=0, prompt=np.asarray(toks[0]),
                               max_new_tokens=8),
                       Request(rid=1, prompt=np.asarray(toks[0]),
                               max_new_tokens=8, arrival_time=0.5)])
    assert out.prefix_hit_tokens > 0
    np.testing.assert_array_equal(ref.results[0], out.results[0])
    np.testing.assert_array_equal(ref.results[0], out.results[1])


# ---------------------------------------------------------------------------
# Sampled speculation: determinism + per-slot params through p AND q
# ---------------------------------------------------------------------------


def test_sampled_spec_deterministic_across_slot_assignments(spec_runs):
    """Sampled speculative streams are keyed by absolute token index, so
    submission order (slot assignment) and rerun don't change them."""
    toks, _, _, _, _, sep_eng, _ = spec_runs
    sps = [SamplingParams(temperature=0.9, top_k=8, top_p=0.95,
                          seed=100 + i) for i in range(4)]
    a = sep_eng.run(_reqs(toks, [0, 1, 2, 3], sps))
    b = sep_eng.run(_reqs(toks, [3, 2, 1, 0], sps))
    for i in range(4):
        np.testing.assert_array_equal(a.results[i], b.results[i])
    # still one draft + one verify compile after the sampled mix
    assert sep_eng._spec_draft._cache_size() == 1
    assert sep_eng._spec_verify._cache_size() == 1


def test_sampled_spec_with_processors_runs_and_is_deterministic(spec_runs):
    """repetition_penalty + logit_bias thread through apply_processors on
    both the draft (q) and verify (p) sides; the stream must reproduce."""
    toks, _, _, _, _, sep_eng, _ = spec_runs
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=11,
                        repetition_penalty=1.3, logit_bias={3: -2.0, 7: 1.5})
    mk = lambda: [Request(rid=0, prompt=np.asarray(toks[0]),
                          max_new_tokens=8, sampling=sp)]
    a = sep_eng.run(mk())
    b = sep_eng.run(mk())
    np.testing.assert_array_equal(a.results[0], b.results[0])
    assert len(a.results[0]) == 8


# ---------------------------------------------------------------------------
# Acceptance-rule statistics (Leviathan et al.): empirical vs analytic
# ---------------------------------------------------------------------------


def test_acceptance_rate_matches_analytic_min_p_over_q():
    """Monte-Carlo over the engine's own primitives (slot_dist, slot_draw,
    spec_uniform tags): the proposal-acceptance rate converges to
    sum_t q(t) * min(1, p(t)/q(t)), and the EMITTED marginal (accepted
    proposals + residual corrections) converges to p itself."""
    v, n = 12, 4096
    kq = jax.random.PRNGKey(20)
    lq = jax.random.normal(kq, (1, v)) * 1.5
    lp = jax.random.normal(jax.random.fold_in(kq, 1), (1, v)) * 1.5
    one = jnp.ones((n,), jnp.float32)
    zero_i = jnp.zeros((n,), jnp.int32)
    q = sampling.slot_dist(jnp.tile(lq, (n, 1)), one, zero_i, one, one * 0.0)
    p = sampling.slot_dist(jnp.tile(lp, (n, 1)), one, zero_i, one, one * 0.0)
    pos = jnp.arange(n, dtype=jnp.int32)      # one window position each
    prop = sampling.slot_draw(q, sampling.spec_uniform(0, pos,
                                                       sampling.TAG_PROPOSE))
    rows = jnp.arange(n)
    ratio = p[rows, prop] / jnp.maximum(q[rows, prop], 1e-20)
    accept = np.asarray(
        sampling.spec_uniform(0, pos, sampling.TAG_ACCEPT)
        < jnp.minimum(1.0, ratio))
    analytic = float(jnp.sum(q[0] * jnp.minimum(1.0, p[0] / jnp.maximum(
        q[0], 1e-20))))
    se = np.sqrt(analytic * (1 - analytic) / n)
    assert abs(accept.mean() - analytic) < 4 * se + 1e-6
    # rejected positions resample from the normalized residual max(p-q, 0)
    resid = jnp.maximum(p - q, 0.0)
    resid = resid / jnp.maximum(jnp.sum(resid, -1, keepdims=True), 1e-20)
    corr = sampling.slot_draw(resid, sampling.spec_uniform(
        0, pos, sampling.TAG_CORRECT))
    out = np.where(accept, np.asarray(prop), np.asarray(corr))
    emp = np.bincount(out, minlength=v) / n
    tv = 0.5 * np.abs(emp - np.asarray(p[0])).sum()
    assert tv < 0.05, f"total variation {tv:.3f} vs target p"


# ---------------------------------------------------------------------------
# Counters + RequestOutput metrics
# ---------------------------------------------------------------------------


def test_per_request_spec_counters_and_metrics(spec_runs):
    toks, _, _, _, self_out, _, sep_out = spec_runs
    for out in (self_out, sep_out):
        assert set(out.per_request) == {0, 1, 2, 3}
        for rid, st in out.per_request.items():
            assert st["spec_windows"] > 0
            assert 0 <= st["spec_accepted"] <= GAMMA * st["spec_windows"]
        assert sum(st["spec_windows"] for st in out.per_request.values()) \
            == out.spec_windows
        assert sum(st["spec_accepted"] for st in out.per_request.values()) \
            == out.spec_accepted
        for o in out.outputs.values():
            assert o.metrics["spec_windows"] == \
                out.per_request[o.rid]["spec_windows"]
            assert o.metrics["spec_accepted"] == \
                out.per_request[o.rid]["spec_accepted"]
        assert out.spec_wasted == out.spec_drafted - out.spec_accepted


# ---------------------------------------------------------------------------
# Prompt logprobs (SamplingParams.prompt_logprobs)
# ---------------------------------------------------------------------------


def _forward_plp(model, params, prompt):
    """Reference: position k's log-softmax row scores prompt token k+1."""
    lg = jax.jit(model.forward)(params, {"tokens": jnp.asarray(prompt)[None]})
    ls = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
    return np.asarray(jnp.take_along_axis(
        ls[:, :-1], jnp.asarray(prompt)[None, 1:, None], axis=-1)[0, :, 0])


def test_prompt_logprobs_continuous_chunked_exact(small, spec_runs):
    """Chunked prefill (3 chunks of 5 over a 12-token prompt) must score
    the prompt exactly as one jitted forward."""
    cfg, model, params = small
    toks, ref_eng, _, _, _, sep_eng, _ = spec_runs
    sp = SamplingParams(prompt_logprobs=True)
    for eng in (ref_eng, sep_eng):        # plain AND speculative engines
        out = eng.run([Request(rid=0, prompt=np.asarray(toks[0]),
                               max_new_tokens=4, sampling=sp)])
        got = out.outputs[0].prompt_logprobs
        assert got is not None and len(got) == 11
        np.testing.assert_allclose(np.asarray(got),
                                   _forward_plp(model, params, toks[0]),
                                   rtol=2e-4, atol=2e-4)


def test_prompt_logprobs_static_backend(small):
    cfg, model, params = small
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (2, 10), 0,
                                         cfg.vocab_size))
    llm = LLMEngine(model, params, backend="static", max_len=24)
    outs = llm.generate(toks, SamplingParams(prompt_logprobs=True),
                        max_new_tokens=4)
    for i in range(2):
        got = outs[i].prompt_logprobs
        assert got is not None and len(got) == 9
        np.testing.assert_allclose(np.asarray(got),
                                   _forward_plp(model, params, toks[i]),
                                   rtol=2e-4, atol=2e-4)


def test_prompt_logprobs_legacy_speculative_raises(small):
    cfg, model, params = small
    llm = LLMEngine(model, params, backend="speculative", max_len=24)
    with pytest.raises(ValueError, match="prompt"):
        llm.generate([np.arange(8) % cfg.vocab_size],
                     SamplingParams(prompt_logprobs=True), max_new_tokens=4)


# ---------------------------------------------------------------------------
# LLMEngine routing + DeploymentSpec accounting
# ---------------------------------------------------------------------------


def test_llm_speculative_kwarg_routes_to_continuous_only(small, draft):
    cfg, model, params = small
    dm, dp = draft
    sc = SpeculativeConfig(draft_model=dm, draft_params=dp, gamma=2)
    with pytest.raises(ValueError, match="continuous"):
        LLMEngine(model, params, backend="static", max_len=24,
                  speculative=sc)
    llm = LLMEngine(model, params, backend="continuous", max_len=24,
                    num_slots=2, page_size=4, speculative=sc)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                         cfg.vocab_size))
    ref = LLMEngine(model, params, backend="continuous", max_len=24,
                    num_slots=2, page_size=4)
    a = llm.generate(toks, max_new_tokens=6)
    b = ref.generate(toks, max_new_tokens=6)
    for i in range(2):
        assert a[i].token_ids == b[i].token_ids
        assert a[i].metrics["spec_windows"] > 0
    assert llm.last_stats.spec_windows > 0


def test_spec_config_validation(small):
    cfg, model, params = small
    with pytest.raises(ValueError):
        SpeculativeConfig(gamma=0)


def test_legacy_speculative_backend_accepts_deployment_spec(small, draft):
    """LLMEngine(backend='speculative', spec=...) used to raise; now the
    spec prices the draft too and the resolved point is exposed."""
    cfg, model, params = small
    dm, dp = draft
    llm = LLMEngine(model, params, backend="speculative",
                    spec=DeploymentSpec(sku="rpu-cu", max_len=64),
                    draft_model=dm, draft_params=dp, gamma=4)
    dep = llm.deployment
    assert dep is not None
    assert dep.spec_gamma == 4
    assert dep.draft_weight_bytes_per_device > 0
    assert dep.spec_window_seconds > 0


def test_spec_decode_benchmark_smoke():
    """Fast-tier smoke of the measured Fig-14 benchmark: a tiny
    target/draft pair through the real engines, outputs byte-identical
    (asserted inside), rows + speedup returned.  The >=1.3x gate runs in
    the slow CI tier at full size."""
    from benchmarks.spec_decode import run_measured
    rows, speedup = run_measured(gamma=2, slots=2, n_req=3, max_new=8,
                                 n_layers=2, draft_layers=1, damp=0.0,
                                 seed=0, reps=1)
    assert speedup > 0
    metrics = {r.metric for r in rows}
    assert "measured speedup" in metrics
    assert "accepted/window (measured)" in metrics
    assert "accepted/window (modeled)" in metrics


def test_deployment_resolve_draft_window_model(small, draft):
    cfg, model, params = small
    dm, dp = draft
    spec = DeploymentSpec(sku="rpu-cu", max_len=64)
    plain = spec.resolve(model)
    a, g = 0.6, 4
    res = spec.resolve(model, draft=dm, draft_params=dp, gamma=g,
                       spec_accept_rate=a)
    # draft weights join the capacity budget; draft KV pages ride in the
    # SAME page-id space, so the per-token pool cost is the combined one
    assert res.draft_weight_bytes_per_device > 0
    assert res.kv_token_bytes == \
        plain.kv_token_bytes + res.draft_kv_token_bytes
    assert res.num_pages <= plain.num_pages
    expected = a * (1.0 - a ** g) / (1.0 - a)
    assert res.spec_expected_accepted == pytest.approx(expected)
    assert res.spec_window_seconds > res.step_seconds
    assert "spec" in res.describe()
    d = res.as_dict()
    for k in ("spec_gamma", "spec_expected_accepted", "spec_window_seconds",
              "spec_tokens_per_s_ceiling", "spec_accept_rate"):
        assert k in d, k
