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

from repro.kernels.decode_attention.paged_kernel import paged_decode_attention
from repro.kernels.decode_attention.ops import paged_gqa_decode_attention
from repro.kernels.decode_attention.ref import paged_decode_attention_ref


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
