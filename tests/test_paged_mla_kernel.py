"""The paged latent (MLA) decode kernel in interpret mode against the
gather-then-dense oracle: ragged positions, a slot at position 0, live
ranges across chunk boundaries, pages shared by a prefix, layer-stacked
pools, and every page off the walk poisoned with NaN."""
import numpy as np
import pytest

import repro.models  # noqa: F401  (import order: models before kernels.ref)
import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import paged_mla_decode_attention
from repro.kernels.decode_attention.paged_kernel import (live_walk,
                                                         pages_per_step)
from repro.kernels.decode_attention.paged_mla_kernel import (
    latent_lanes, paged_mla_decode)
from repro.kernels.decode_attention.ref import paged_mla_decode_ref

RANK, ROPE, PAGE, N_BLOCKS, H = 384, 64, 32, 12, 4
W = latent_lanes(RANK, ROPE)


def _case(pos, *, layers=None, shared=0, dtype=jnp.float32, seed=0):
    """A latent pool, per-slot permuted tables (the first ``shared`` blocks
    of every slot on the same pages, as a shared prefix leaves them), and
    absorbed queries with zero pad lanes."""
    b = len(pos)
    rng = np.random.default_rng(seed)
    n_pages = 1 + shared + b * (N_BLOCKS - shared)
    own = rng.permutation(np.arange(1 + shared, n_pages)).reshape(
        b, N_BLOCKS - shared)
    prefix = np.broadcast_to(np.arange(1, 1 + shared), (b, shared))
    table = np.concatenate([prefix, own], axis=1).astype(np.int32)
    key = jax.random.PRNGKey(seed)
    shape = (layers or 1, n_pages, PAGE, W)
    lat = jax.random.normal(key, shape, jnp.float32)
    lat = lat.at[..., RANK + ROPE:].set(0.0).astype(dtype)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, H, W), jnp.float32)
    q = q.at[..., RANK + ROPE:].set(0.0)
    return q, lat, table, np.asarray(pos, np.int32)


@pytest.mark.parametrize("pos,layers,shared", [
    ([0, 37, 255, 256, 383], None, 0),     # position 0; chunk edge at 256
    ([300, 31, 32, 200], 3, 0),            # layer-stacked pool
    ([100, 290, 5, 383], None, 4),         # four blocks shared by prefix
])
def test_kernel_matches_oracle_and_reads_only_live_pages(pos, layers,
                                                         shared):
    q, lat, table, pos = _case(pos, layers=layers, shared=shared)
    ppb = pages_per_step(PAGE, W, 4, N_BLOCKS, None)
    assert ppb == 8                        # two chunks past position 255
    layer = 1 if layers else 0
    ref = paged_mla_decode_ref(q, lat[layer], jnp.asarray(table),
                               jnp.asarray(pos), rank=RANK, scale=0.07)
    lo, live, _ = live_walk(pos, PAGE, None, ppb)
    live_pages = np.concatenate([table[i, lo[i]:lo[i] + live[i]]
                                 for i in range(len(pos))])
    dead = np.setdiff1d(np.arange(lat.shape[1]), live_pages)
    assert 0 in dead
    poisoned = lat.at[:, dead].set(jnp.nan)
    poisoned = poisoned.at[np.arange(lat.shape[0]) != layer].set(jnp.nan)
    out = paged_mla_decode(q, poisoned if layers else poisoned[0],
                           jnp.asarray(table), jnp.asarray(pos), rank=RANK,
                           scale=0.07, layer=layer if layers else None,
                           interpret=True)
    out = np.asarray(out)
    assert out.shape == (len(pos), H, RANK) and np.isfinite(out).all()
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_kernel_at_the_serve_dtype():
    """bf16 pools: the kernel scores bf16 queries on the MXU and weights
    the context with bf16 probabilities (f32 accumulation); the oracle is
    f32 throughout, so they differ by bf16 rounding of q and p."""
    q, lat, table, pos = _case([3, 150, 290, 383], dtype=jnp.bfloat16,
                               seed=4)
    ref = paged_mla_decode_ref(q, lat[0], jnp.asarray(table),
                               jnp.asarray(pos), rank=RANK, scale=0.05)
    out = paged_mla_decode(q, lat[0], jnp.asarray(table), jnp.asarray(pos),
                           rank=RANK, scale=0.05, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0,
                               atol=2e-2)


def test_op_wrapper_routes_by_impl():
    q, lat, table, pos = _case([10, 70])
    args = (q, lat[0], jnp.asarray(table), jnp.asarray(pos))
    ref = paged_mla_decode_attention(*args, rank=RANK, scale=0.1,
                                     impl="reference")
    fused = paged_mla_decode_attention(*args, rank=RANK, scale=0.1,
                                       impl="fused")
    auto = paged_mla_decode_attention(*args, rank=RANK, scale=0.1)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))  # CPU
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="impl"):
        paged_mla_decode_attention(*args, rank=RANK, scale=0.1, impl="x")


def test_latent_rows_are_whole_lane_tiles():
    assert latent_lanes(512, 64) == 640          # DeepSeek-V2-Lite
    assert latent_lanes(32, 8) == 128
    assert pages_per_step(16, 640, 2, 1024, None) == 16
