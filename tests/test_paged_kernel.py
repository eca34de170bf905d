"""Gather-fused paged decode kernel vs the gather-then-dense oracle.

Runs the Pallas kernel in interpret mode on CPU (fast tier), so the fused
path — page-table-driven grid, GQA head packing, prefix and sliding-window
masks — is exercised in CI even though the serve engine takes the oracle on
CPU.  ``accum="exact"`` must match ``paged_decode_attention_ref``
bit-for-bit; ``accum="online"`` (the production flash-decode accumulator)
is held to a few-ulp tolerance against the same oracle.
"""
import numpy as np
import pytest

import repro.models  # noqa: F401  (import order: models before kernels.ref)
import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.paged_kernel import (
    live_walk, pages_per_step, paged_decode_attention)
from repro.kernels.decode_attention.ops import paged_gqa_decode_attention
from repro.kernels.decode_attention.ref import paged_decode_attention_ref
from repro.quant import kv as kvq


def _paged_case(seed, B, H, KVH, D, page, n_blocks, dtype=jnp.float32,
                permute=True, extra_pages=0):
    """Random pool + per-row permuted page tables + ragged positions."""
    key = jax.random.PRNGKey(seed)
    S = page * n_blocks
    P = 1 + B * n_blocks + extra_pages
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, P)) if permute else np.arange(1, P)
    table = jnp.asarray(ids[:B * n_blocks].reshape(B, n_blocks), jnp.int32)
    q = jax.random.normal(key, (B, H, D), dtype)
    k_pages = jax.random.normal(jax.random.fold_in(key, 1),
                                (P, page, KVH, D), dtype)
    v_pages = jax.random.normal(jax.random.fold_in(key, 2),
                                (P, page, KVH, D), dtype)
    pos = jnp.asarray(rng.integers(0, S, B), jnp.int32)
    return q, k_pages, v_pages, table, pos


def _lanes(pages):
    """(P, page, KVH, D) -> the kernel's serve layout (P, page, KVH * D)."""
    return pages.reshape(pages.shape[:2] + (-1,))


@pytest.mark.parametrize("B,H,KVH,D,page,n_blocks,dtype", [
    (3, 8, 2, 32, 8, 5, jnp.float32),     # GQA 4:1
    (2, 16, 2, 64, 16, 3, jnp.float32),   # GQA 8:1
    (1, 4, 4, 16, 4, 7, jnp.float32),     # MHA, many small pages
    (2, 8, 2, 32, 8, 4, jnp.bfloat16),    # serve dtype
])
def test_fused_exact_matches_oracle_bitwise(B, H, KVH, D, page, n_blocks,
                                            dtype):
    q, kp, vp, table, pos = _paged_case(0, B, H, KVH, D, page, n_blocks,
                                        dtype=dtype)
    ref = paged_decode_attention_ref(q, kp, vp, table, pos)
    out = paged_decode_attention(q, _lanes(kp), _lanes(vp), table, pos,
                                 accum="exact", interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("window", [1, 3, 11])
def test_fused_exact_sliding_window_bitwise(window):
    q, kp, vp, table, pos = _paged_case(window, 2, 8, 2, 32, 8, 5)
    ref = paged_decode_attention_ref(q, kp, vp, table, pos, window=window)
    out = paged_decode_attention(q, _lanes(kp), _lanes(vp), table, pos,
                                 window=window, accum="exact", interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("window", [None, 5])
def test_fused_online_close_to_oracle(window):
    """The O(1)-scratch flash-decode accumulator: same mask/gather logic as
    the exact mode, rescaling differences bounded to a few ulps."""
    q, kp, vp, table, pos = _paged_case(3, 3, 8, 2, 32, 8, 5)
    ref = np.asarray(paged_decode_attention_ref(q, kp, vp, table, pos,
                                                window=window), np.float32)
    out = np.asarray(paged_decode_attention(q, _lanes(kp), _lanes(vp), table,
                                            pos, window=window,
                                            accum="online", interpret=True),
                     np.float32)
    np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)


def test_fused_ignores_scratch_page_tail():
    """Unallocated table entries point at the scratch page (id 0); whatever
    garbage lives there must not leak into the output."""
    q, kp, vp, table, pos = _paged_case(5, 2, 8, 2, 32, 8, 4, extra_pages=1)
    # positions confined to the first two blocks; tail blocks -> scratch
    pos = jnp.asarray([7, 12], jnp.int32)
    table_scratch = jnp.asarray(np.where(np.arange(4)[None, :] < 2,
                                         np.asarray(table), 0), jnp.int32)
    kp = kp.at[0].set(1e4)                       # poison the scratch page
    vp = vp.at[0].set(-1e4)
    ref = paged_decode_attention_ref(q, kp, vp, table_scratch, pos)
    for accum in ("exact", "online"):
        out = paged_decode_attention(q, _lanes(kp), _lanes(vp),
                                     table_scratch, pos, accum=accum,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-6, atol=2e-6)


def test_op_wrapper_impl_routing():
    q, kp, vp, table, pos = _paged_case(7, 2, 4, 2, 16, 4, 3)
    kp, vp = _lanes(kp), _lanes(vp)                  # the serve layout
    ref = paged_gqa_decode_attention(q, kp, vp, table, pos, impl="reference")
    auto = paged_gqa_decode_attention(q, kp, vp, table, pos)   # CPU -> oracle
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))
    fused = paged_gqa_decode_attention(q, kp, vp, table, pos, impl="fused")
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-6, atol=2e-6)
    with pytest.raises(ValueError):
        paged_gqa_decode_attention(q, kp, vp, table, pos, impl="nope")


# The chunk walk at shapes whose chunks hold few pages: f32 pages of
# 16 x 4096 lanes (256 KiB) make chunks of 2 pages (32 tokens); fp8/int8
# codes (64 KiB a page) chunks of 8 (128 tokens).  12 blocks: max_len 192.
# The "page_walk" cases' widths are not whole 128-lane tiles, so they take
# the page walk, one page a step.
WALK = dict(H=32, KVH=32, D=128, page=16, n_blocks=12)
WALK_CASES = {
    "pos_0_15_16_17": dict(pos=[0, 15, 16, 17]),
    "chunk_edge": dict(pos=[31, 32, 33]),
    "last": dict(pos=[191]),
    "window_below": dict(pos=[20, 38], window=40),
    "window_at": dict(pos=[39, 40, 41], window=40),
    "window_far": dict(pos=[150, 191], window=40),
    "window_chunk_edge": dict(pos=[71, 72, 73], window=40),
    "mixed": dict(pos=[0, 33, 100, 191]),
    "gqa_rep4": dict(pos=[5, 70], H=128),
    "layer_stacked": dict(pos=[17, 64], layers=3),
    "fp8": dict(pos=[127, 128, 129], cache="fp8"),
    "fp8_window": dict(pos=[150, 45], cache="fp8", window=40),
    "int8": dict(pos=[0, 129, 191], cache="int8"),
    "page_walk_mixed": dict(pos=[0, 17, 100, 191], H=10, KVH=5, D=64),
    "page_walk_window": dict(pos=[20, 40, 150, 191], H=8, KVH=2, D=80,
                             window=40),
    "page_walk_int8_stacked": dict(pos=[16, 129], H=5, KVH=5, D=64,
                                   cache="int8", layers=3),
    "page_walk_fp8_window": dict(pos=[31, 100], H=16, KVH=4, D=80,
                                 cache="fp8", window=40),
}


@pytest.mark.parametrize("name", list(WALK_CASES))
def test_online_walk_reads_only_live_pages(name):
    """Every page outside the slots' live ranges (the scratch page 0, the
    blocks past ``pos``, those behind the window, the other layers of a
    stacked pool) holds NaN: the output stays finite and matches the
    oracle on clean pools, so the walk never reads them."""
    c = {**WALK, **WALK_CASES[name]}
    h, kvh, d, page, n_blocks = (c[k] for k in ("H", "KVH", "D", "page",
                                                "n_blocks"))
    window, cache, n_layers = c.get("window"), c.get("cache"), c.get("layers")
    pos = np.asarray(c["pos"], np.int32)
    b = len(pos)
    rng = np.random.default_rng(len(name))
    n_pages = 1 + b * n_blocks
    table = rng.permutation(np.arange(1, n_pages)).reshape(b, n_blocks)
    key = jax.random.PRNGKey(len(name))
    q = jax.random.normal(key, (b, h, d), jnp.float32)
    shape = (n_layers or 1, n_pages, page, kvh, d)
    kp = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    vp = jax.random.normal(jax.random.fold_in(key, 2), shape, jnp.float32)
    itemsize = 4 if cache is None else 1
    ppb = pages_per_step(page, kvh * d, itemsize, n_blocks, window)
    # a window of 40 caps a chunk at the 4 blocks it can touch
    assert ppb == (1 if kvh * d % 128 else
                   min(2 if cache is None else 8, 4 if window else n_blocks))
    lo, live, _ = live_walk(pos, page, window, ppb)
    live_pages = np.concatenate([table[i, lo[i]:lo[i] + live[i]]
                                 for i in range(b)])
    dead = np.setdiff1d(np.arange(n_pages), live_pages)
    assert 0 in dead
    layer = 1 if n_layers else 0
    others = np.arange(shape[0]) != layer

    def poison(a):                         # (L, P, ...) -> NaN off the walk
        a = a.at[:, dead].set(jnp.nan)
        return a.at[others].set(jnp.nan)

    if cache is None:
        ref = paged_decode_attention_ref(q, kp[layer], vp[layer],
                                         jnp.asarray(table), pos,
                                         window=window)
        k_in, v_in, scales = poison(kp), poison(vp), {}
    else:
        (kc, ks), (vc, vs) = kvq.kv_quantize(kp, cache), kvq.kv_quantize(
            vp, cache)
        ref = paged_decode_attention_ref(
            q, kc[layer], vc[layer], jnp.asarray(table), pos,
            k_scales=ks[layer], v_scales=vs[layer], window=window)
        if cache == "fp8":                 # int8 codes have no NaN
            kc, vc = poison(kc), poison(vc)
        k_in, v_in = kc, vc
        scales = dict(k_scales=poison(ks), v_scales=poison(vs))
        if not n_layers:
            scales = {k: v[0] for k, v in scales.items()}
    lanes = lambda a: a.reshape(a.shape[:-2] + (-1,))
    k_in, v_in = lanes(k_in), lanes(v_in)
    if not n_layers:
        k_in, v_in = k_in[0], v_in[0]
    out = np.asarray(paged_decode_attention(
        q, k_in, v_in, jnp.asarray(table), pos,
        layer=layer if n_layers else None, window=window, interpret=True,
        **scales), np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, np.asarray(ref, np.float32),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("kvh,d,itemsize,n_blocks,window,ppb", [
    (32, 96, 2, 256, None, 4),      # phi3-mini: 96 KiB pages
    (8, 96, 2, 256, None, 16),      # phi3-mini over 4 chips
    (32, 96, 1, 256, None, 8),      # phi3-mini, fp8 KV
    (8, 80, 2, 1024, 4096, 16),     # h2o-danube: 20 KiB pages
    (4, 80, 2, 1024, 4096, 1),      # h2o-danube over 2 chips: 320 lanes
    (2, 80, 2, 1024, 4096, 1),      # h2o-danube over 4 chips: 160 lanes
    (5, 64, 2, 512, None, 1),       # hymba-1.5b: 320 lanes
    (8, 128, 2, 4, 4096, 4),        # a short table caps the chunk
])
def test_online_walk_form_follows_the_width(kvh, d, itemsize, n_blocks,
                                            window, ppb):
    """Pages a step of the walk folds at the served shapes: a chunk of up
    to 512 KiB of K where the width is whole 128-lane tiles, else one
    page (the page walk)."""
    assert pages_per_step(16, kvh * d, itemsize, n_blocks, window) == ppb
