"""The dropless expert share (``moe.moe_share``): one device's cut of an
expert-parallel MoE layer, its serve path, and the counters its decode
step reports."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced_config
from repro.models import moe as moe_lib
from repro.models.common import ModelConfig
from repro.models.model import build_model
from repro.runtime.llm import LLMEngine
from repro.runtime.sampling import SamplingParams

SHARES = 8


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@pytest.mark.parametrize("norm", [False, True])
def test_eight_shares_plus_shared_experts_equal_the_uncut_layer(norm):
    """Each of 8 shares holds E/8 experts and routes over all E; their
    outputs summed, plus the shared experts counted once, are the uncut
    layer's (every expert over every token), renormalised or not.  Share
    j holds experts j*E/8 .. as its own 0 .., its router's columns
    rotated to match."""
    cfg = ModelConfig(d_model=32, moe=True, n_experts=16,
                      n_experts_per_token=4, n_shared_experts=1, moe_d_ff=24,
                      norm_topk_prob=norm)
    p = _f32(moe_lib.init_moe(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32), jnp.float32)
    uncut = moe_lib.moe_forward(x, p, cfg, impl="dense")
    held = cfg.n_experts // SHARES
    share_cfg = dataclasses.replace(cfg, n_experts_held=held)
    total = 0.0
    for j in range(SHARES):
        cut = slice(j * held, (j + 1) * held)
        pj = {"router": jnp.roll(p["router"], -j * held, axis=1),
              "w_gate": p["w_gate"][cut], "w_up": p["w_up"][cut],
              "w_down": p["w_down"][cut]}
        total = total + moe_lib.moe_share(x, pj, share_cfg)
    shared = moe_lib.common.swiglu(x, p["shared"]["w_gate"],
                                   p["shared"]["w_up"], p["shared"]["w_down"])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)


def test_routing_keeps_the_top_k_probabilities_unless_normalised():
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 8), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(3), (8, 16), jnp.float32)
    w, ids = moe_lib._routing(x, router, 4, normalize=False)
    probs = jax.nn.softmax(x @ router, axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(np.asarray(probs),
                                          np.asarray(ids), 1), rtol=1e-6)
    assert float(w.sum(-1).max()) < 1.0
    wn, _ = moe_lib._routing(x, router, 4)
    np.testing.assert_allclose(np.asarray(wn.sum(-1)), 1.0, rtol=1e-6)


def _share_config():
    """A tiny config holding 2 of 16 experts, with two MoE layers (one
    scanned segment) after a dense one."""
    cfg = reduced_config(get_config("deepseek-v2-lite-16b-ep8"))
    return dataclasses.replace(cfg, n_layers=3, n_experts=16,
                               n_experts_per_token=4, n_experts_held=2)


def test_share_config_serves_without_capacity_or_dense_paths(monkeypatch):
    """Prefill chunks and decode steps of a held share run ``moe_share``
    only: the capacity (and every other routed) path is never traced."""
    def refuse(*a, **k):
        raise AssertionError("the share's serve path left moe_share")
    monkeypatch.setattr(moe_lib, "_routed", refuse)
    monkeypatch.setattr(moe_lib, "moe_capacity", refuse)
    cfg = _share_config()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert params["stacks"][1][0]["moe"]["w_gate"].shape[:2] == (2, 2)
    assert params["stacks"][1][0]["moe"]["router"].shape[-1] == 16
    llm = LLMEngine(model, params, backend="continuous", max_len=64,
                    num_slots=2, page_size=4, num_pages=40, prefill_chunk=8)
    rng = np.random.default_rng(0)
    outs = llm.generate([rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                         for n in (13, 5, 9)],
                        SamplingParams(max_tokens=6))
    assert [len(o.token_ids) for o in outs] == [6, 6, 6]


def test_decode_hits_match_a_host_recount_of_the_routing(monkeypatch):
    """The decode step's ``(MoE layers, B, held)`` hit matrix is the rows
    each held expert was routed, as recounted from the router's own ids."""
    cfg = _share_config()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    seen = []
    routing = moe_lib._routing

    def spy(x2d, router, k, normalize=True):
        w, ids = routing(x2d, router, k, normalize)
        jax.debug.callback(lambda i: seen.append(np.asarray(i)), ids)
        return w, ids

    monkeypatch.setattr(moe_lib, "_routing", spy)
    b, page = 3, 4
    pools = model.init_paged_cache(16, page, dtype=jnp.float32)
    table = jnp.asarray(np.arange(1, 1 + b * 4).reshape(b, 4), jnp.int32)
    tokens = jnp.asarray([5, 17, 200], jnp.int32)
    pos = jnp.asarray([0, 6, 11], jnp.int32)
    _, _, hits = model.decode_step_paged(params, tokens, pools, table, pos,
                                         expert_hits=True)
    hits = np.asarray(hits)
    jax.effects_barrier()
    assert hits.shape == (2, b, 2) and len(seen) == 2
    recount = [np.stack([(ids == e).any(axis=1) for e in range(2)], axis=1)
               for ids in seen]
    key = lambda h: h.tobytes()
    assert sorted(map(key, recount)) == sorted(map(key, list(hits)))


def test_engine_counts_the_decoding_slots_rows(monkeypatch):
    """``moe_rows_held`` and ``moe_experts_active`` of each step record are
    its decode step's hits over the slots that were decoding; the latent
    pages join ``kv_pages_live`` / ``kv_pages_walked``."""
    cfg = _share_config()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    llm = LLMEngine(model, params, backend="continuous", max_len=64,
                    num_slots=3, page_size=4, num_pages=60, prefill_chunk=8)
    eng = llm._eng
    want, step_fn = [], eng._step_fn

    def spy(*args):       # params, pools, states, presence, tokens, pos, table
        out = step_fn(*args)
        live = np.asarray(args[6])[:, 0] != 0          # decoding slots' rows
        h = np.asarray(out[-1])[:, live]
        want.append((int(h.sum()), int(h.any(axis=1).sum())))
        return out

    eng._step_fn = spy
    rng = np.random.default_rng(3)
    for i, n in enumerate((7, 15, 3, 11)):
        llm.add_request(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                        SamplingParams(max_tokens=9), rid=i)
    while llm.has_unfinished():
        llm.step()
    recs = [r for r in llm.step_log() if r.decode_slots]
    got = [(r.moe_rows_held, r.moe_experts_active) for r in recs]
    assert got == want and len(got) > 0
    assert sum(g[0] for g in got) > 0
    # the latent pages the decode kernel reads are counted too
    assert list(eng._kv_walks) == [None]
    assert all(r.kv_pages_walked >= r.kv_pages_live > 0 for r in recs)
