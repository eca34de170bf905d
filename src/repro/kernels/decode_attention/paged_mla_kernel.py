"""Paged latent (MLA) decode attention: one latent stream, read once.

DeepSeek's multi-head latent attention caches, per token and layer, one
``kv_lora_rank`` latent ``c`` and one shared rotary key ``k_rope``.  With
the key and value up-projections absorbed into the query and the output
(``q_lat = q_nope W_uk^T``, ``out = ctx W_uv``), every head scores the same
latent row and takes its value from it:

    s_h(t) = scale * (q_lat_h . c_t + q_rope_h . k_rope_t)
    ctx_h  = sum_t softmax_t(s_h) c_t

so the latent page is both the key and the value, and one read of it
serves all heads.

Pool layout: ``([L,] P, page, W)`` with ``c`` in lanes ``0 .. rank``,
``k_rope`` after it and zeros up to ``W``, a whole number of 128-lane
tiles (a DMA slices a page out of an HBM pool only along whole tiles;
576 = 512 + 64 is not one).  The walk is the GQA kernel's chunk walk
(``paged_kernel._chunk_walk``): the grid is the slots, each slot's live
pages (``live_walk``) are gathered ``pages_per_step`` at a time into a
double-buffered VMEM chunk by their own async copies, and only those
pages are read.  Each chunk folds on the MXU: the ``(H, W)`` query
against the ``(rows, W)`` chunk for the scores, the probabilities against
the chunk's ``rank`` lanes for the context, with f32 running max,
denominator and numerator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.paged_kernel import (
    LANES, NEG_INF, _chunk_walk, _visible, pages_per_step,
)


def latent_lanes(rank: int, rope: int) -> int:
    """Lanes of one token's latent row: ``rank + rope`` rounded up to whole
    128-lane tiles."""
    return -(-(rank + rope) // LANES) * LANES


def _mla_kernel(pt_ref, pos_ref, layer_ref, q_ref, lat_hbm, o_ref, buf, sem,
                at_ref, m_ref, l_ref, acc_ref, *, page: int, ppb: int,
                scale: float, rank: int):
    """Grid step ``b``: fold slot ``b``'s live latent chunks into (m, l,
    acc) and write its ``(H, rank)`` context."""
    pos = pos_ref[pl.program_id(0)]
    rows = ppb * page
    q = q_ref[0]                                         # (H, W)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fold(cur, first):
        kv = buf[cur].reshape(rows, -1)                  # (rows, W)
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        idx = first + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        s = jnp.where(_visible(idx, pos, None), s, NEG_INF)   # (H, rows)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(kv.dtype), kv[:, :rank],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _chunk_walk(pt_ref, pos_ref, ((lat_hbm, buf, layer_ref[0]),), sem,
                at_ref, fold, page=page, ppb=ppb, window=None)
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def paged_mla_decode(
    q: jnp.ndarray,            # (B, H, W): [q_lat | q_rope | 0] per head
    pages: jnp.ndarray,        # ([L,] P, page, W) latent page pool
    page_table: jnp.ndarray,   # (B, n_blocks) int32 logical block -> page
    pos: jnp.ndarray,          # (B,) int32 per-slot position of the new token
    *,
    rank: int,
    scale: float,
    layer=None,                # int32 scalar: layer of a stacked pool
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token paged latent decode attention: the f32 ``(B, H, rank)``
    context of every head over its slot's live latent rows (``pos`` and
    everything before it).  ``q`` is cast to the pool's dtype for the MXU;
    scores, softmax and context accumulate in f32."""
    if layer is None:
        pages, layer = pages[None], 0
    b, h, width = q.shape
    _, _, page, w_pool = pages.shape
    assert width == w_pool and width % LANES == 0, (width, w_pool)
    n_blocks = page_table.shape[1]
    ppb = pages_per_step(page, width, pages.dtype.itemsize, n_blocks, None)
    slot_spec = lambda bb, *_: (bb, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # page_table, pos, layer
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, width), slot_spec),
                  pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
        out_specs=pl.BlockSpec((1, h, rank), slot_spec),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, width), pages.dtype),   # the chunks
            pltpu.SemaphoreType.DMA((2,)),         # one per buffer
            pltpu.SMEM((1,), jnp.int32),           # the next slot's buffer
            pltpu.VMEM((h, 1), jnp.float32),       # running max
            pltpu.VMEM((h, 1), jnp.float32),       # running denom
            pltpu.VMEM((h, rank), jnp.float32),    # running numerator
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, page=page, ppb=ppb, scale=scale,
                          rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        # a slot's last chunk starts the next slot's first: steps in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_mla_decode",
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.astype(pages.dtype), pages)
