"""Gather-fused paged flash-decode attention — pages are first-class all the
way into the kernel.

The serve path used to materialize a dense ``(B, S, KVH, D)`` copy of every
slot's pages before running the dense decode kernel, doubling decode HBM
traffic.  Here the page table itself drives the Pallas grid: the table and
per-slot positions are **scalar-prefetched**, so each grid step's BlockSpec
``index_map`` reads ``page_table[b, j]`` and the pipeline DMAs that physical
K/V page HBM->VMEM directly — the paper's "stream KV from HBM into the SDPA
pipeline" with no dense intermediate.

Pool layout: ``([L,] P, page, KVH * D)`` — every KV head of a token side by
side on the lane axis.  The lane-dense last dim is what keeps the pool
unpadded in HBM (a trailing ``(KVH, D=96)`` would pad D to 128 lanes, or
make XLA pick a layout with the page axis minor and copy the whole pool
into the kernel's row-major layout every step).  ``L`` is the layer axis
of a scanned segment's stacked pools, selected by the scalar-prefetched
``layer``.

Grid: ``(B, n_blocks)``, page walk innermost.  Each step DMAs one whole
physical page of one layer — one contiguous ``(page, KVH * D)`` block.  The
per-head contractions stay lane-dense: ``k * q`` summed per head through a
0/1 ``(KVH * D, KVH)`` segment matrix gives the ``(page, KVH)`` scores, and
the probabilities spread back over each head's lanes through its
transpose.  ``rep = H / KVH`` query heads per kv head (GQA) each take one
such pass, and the mask family covers both the prefix case (``idx <=
pos``) and sliding windows (``pos - window < idx <= pos``).

Under tensor-parallel serving the kernel is already per-shard: the page
pools shard their KV-head lanes over the mesh's model axis
(``AttentionBackend.paged_partition_spec``), so inside the manual
shard_map region KV_HEADS here is the LOCAL head count and each step
streams only the shard's slice of every page — each CU streams its own
KV$ cut, the page table is the same replicated array on every shard, and
no cross-shard traffic happens until the block's closing reduction.

Two accumulator modes:

  * ``accum="online"`` — classic flash-decode: fp32 (m, l, acc) running
    state in VMEM scratch, rescaled per page.  O(1) scratch in sequence
    length; the production TPU path.
  * ``accum="exact"``  — K and V pages are staged into position-ordered
    VMEM scratch during the page walk; the final grid step runs scores,
    softmax and the P·V contraction as single ops, reproducing the
    oracle's op sequence **bit-exactly** (verified in CI against
    ``paged_decode_attention_ref`` in interpret mode).  Scratch is
    O(S_max · KVH · D) per slot — the CPU verification mode (it is not
    sized for the chip's VMEM, and its per-head reshapes are not TPU
    layouts), and the numerics contract the online mode is tested
    against.

Pages whose positions are entirely masked (table tail pointing at the
scratch page, or pages outside a sliding window) are skipped with
``pl.when`` so they contribute neither FLOPs nor accumulator drift.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_EXACT = jax.lax.Precision.HIGHEST


def _visible(idx, pos, window):
    """Which absolute positions ``idx`` the token at ``pos`` attends to."""
    valid = idx <= pos
    if window is not None:
        valid = valid & (idx > pos - window)
    return valid


def _page_live(j, pos, page: int, window):
    """Scalar: does page ``j`` contain any visible position?"""
    lo = j * page
    live = lo <= pos
    if window is not None:
        live = jnp.logical_and(live, lo + page - 1 > pos - window)
    return live


def _head_segments(kvh: int, d: int, transpose: bool = False):
    """0/1 f32 matrix mapping lane ``w`` to KV head ``w // d``:
    ``(kvh * d, kvh)``, or ``(kvh, kvh * d)`` when ``transpose``."""
    shape, lane_axis = ((kvh, kvh * d), 1) if transpose else ((kvh * d, kvh), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, lane_axis)
    head = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - lane_axis)
    return ((lane >= head * d) & (lane < head * d + d)).astype(jnp.float32)


def _online_kernel(pt_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
                   page: int, n_blocks: int, scale: float, window, kvh: int,
                   rep: int, quantized: bool = False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    d = q_ref.shape[-1] // kvh

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(_page_live(j, pos, page, window))
    def _fold():
        seg = _head_segments(kvh, d)                     # (W, KVH)
        seg_t = _head_segments(kvh, d, transpose=True)   # (KVH, W)
        k = k_ref[0].astype(jnp.float32)                 # (page, W)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # dequant fused into the page-streaming loop: each token's
            # per-head f32 scale spread over that head's lanes
            k = k * jnp.dot(ks_ref[0], seg_t, precision=_EXACT)
            v = v * jnp.dot(vs_ref[0], seg_t, precision=_EXACT)
        idx = j * page + jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
        visible = _visible(idx, pos, window)             # (page, 1)
        for r in range(rep):
            q = q_ref[0, r:r + 1].astype(jnp.float32)    # (1, W)
            s = jnp.dot(k * q, seg, precision=_EXACT) * scale  # (page, KVH)
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[r:r + 1]                      # (1, KVH)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[r:r + 1] = l_ref[r:r + 1] * corr + jnp.sum(
                p, axis=0, keepdims=True)
            pv = jnp.dot(p, seg_t, precision=_EXACT) * v  # (page, W)
            acc_ref[r:r + 1] = (
                acc_ref[r:r + 1] * jnp.dot(corr, seg_t, precision=_EXACT)
                + jnp.sum(pv, axis=0, keepdims=True))
            m_ref[r:r + 1] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        denom = jnp.dot(jnp.maximum(l_ref[...], 1e-30),
                        _head_segments(kvh, d, transpose=True),
                        precision=_EXACT)                # (rep, W)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _exact_kernel(pt_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
                  page: int, n_blocks: int, scale: float, window, kvh: int,
                  rep: int, quantized: bool = False):
    """Stage dequantized K and V position-ordered; scores, softmax and the
    contraction run once at the end over the whole sequence — the same op
    sequence and shapes as the gather-then-dense oracle, so the output is
    bit-identical to ``paged_decode_attention_ref`` (scores computed page
    by page would not be: the CPU's dot blocks differently per width)."""
    if quantized:
        ksc_ref, vsc_ref, o_ref, ks_ref, vs_ref = rest
    else:
        o_ref, ks_ref, vs_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    d = q_ref.shape[-1] // kvh

    k = k_ref[0].astype(jnp.float32).reshape(page, kvh, d)
    v = v_ref[0].astype(jnp.float32).reshape(page, kvh, d)
    if quantized:
        k = k * ksc_ref[0][:, :, None]
        v = v * vsc_ref[0][:, :, None]
    ks_ref[pl.ds(j * page, page)] = k
    vs_ref[pl.ds(j * page, page)] = v

    @pl.when(j == n_blocks - 1)
    def _finalize():
        q = jnp.swapaxes(q_ref[0].astype(jnp.float32).reshape(rep, kvh, d),
                         0, 1)                           # (KVH, rep, D)
        s = jnp.einsum("grd,sgd->grs", q, ks_ref[...]) * scale
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_blocks * page), 2)
        s = jnp.where(_visible(idx, pos, window), s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)                   # (KVH, rep, S)
        out = jnp.einsum("grs,sgd->grd", p, vs_ref[...])
        o_ref[0] = jnp.swapaxes(out, 0, 1).reshape(rep, kvh * d).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "accum", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,            # (B, H, D)
    k_pages: jnp.ndarray,      # ([L,] P, page, KVH * D) physical page pool
    v_pages: jnp.ndarray,      # ([L,] P, page, KVH * D)
    page_table: jnp.ndarray,   # (B, n_blocks) int32 logical block -> page
    pos: jnp.ndarray,          # (B,) int32 per-slot position of the new token
    *,
    layer=None,                # int32 scalar: layer of a stacked pool
    k_scales: jnp.ndarray | None = None,   # ([L,] P, page, KVH) f32 scales
    v_scales: jnp.ndarray | None = None,
    window: int | None = None,
    accum: str = "online",
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token paged GQA decode attention; returns (B, H, D) in q.dtype.

    ``layer`` selects one layer of layer-stacked ``(L, P, page, ...)``
    pools, so a scanned decode step hands the kernel its whole pool and
    never slices (copies) a layer out of it; without ``layer`` the pools
    are one layer's ``(P, page, ...)``.

    With ``k_scales``/``v_scales`` the pools hold quantized codes (fp8
    e4m3 or int8) and dequantization fuses into the page-streaming loop:
    each page's codes are cast to f32 and multiplied by its per-token
    scales right after the DMA, before the flash-decode fold."""
    if layer is None:
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
        layer = 0
    b, h, d = q.shape
    _, _, page, width = v_pages.shape
    kvh = width // d
    n_blocks = page_table.shape[1]
    assert kvh * d == width and h % kvh == 0, (h, d, width)
    quantized = k_scales is not None
    assert (v_scales is not None) == quantized, "pass both scales or neither"
    rep = h // kvh
    scale = 1.0 / math.sqrt(d)

    # (B, H, D) -> (B, rep, KVH * D): query head g * rep + r sits in row r,
    # lanes of kv head g — the pool's lane order
    qg = q.reshape(b, kvh, rep, d).transpose(0, 2, 1, 3).reshape(b, rep, width)
    grid = (b, n_blocks)
    kernel = _online_kernel if accum == "online" else _exact_kernel
    if accum == "online":
        scratch = [
            pltpu.VMEM((rep, kvh), jnp.float32),         # running max
            pltpu.VMEM((rep, kvh), jnp.float32),         # running denom
            pltpu.VMEM((rep, width), jnp.float32),       # running numerator
        ]
    elif accum == "exact":
        scratch = [
            pltpu.VMEM((n_blocks * page, kvh, d), jnp.float32),    # staged K
            pltpu.VMEM((n_blocks * page, kvh, d), jnp.float32),    # staged V
        ]
    else:
        raise ValueError(f"accum={accum!r} (want 'online' or 'exact')")

    # one block = one whole physical page of one layer, all KV heads: its
    # last two dims equal the pool's, which is what the TPU lowering
    # requires of a block narrower than (8, 128)
    lyr = pl.Squeezed()
    page_spec = lambda bb, j, pt, ps, ly: (ly[0], pt[bb, j], 0, 0)
    slot_spec = lambda bb, j, pt, ps, ly: (bb, 0, 0)
    in_specs = [
        pl.BlockSpec((1, rep, width), slot_spec),
        pl.BlockSpec((lyr, 1, page, width), page_spec),
        pl.BlockSpec((lyr, 1, page, width), page_spec),
    ]
    inputs = [qg, k_pages, v_pages]
    if quantized:
        # scale pages ride the same page-table-driven index map
        in_specs += [pl.BlockSpec((lyr, 1, page, kvh), page_spec),
                     pl.BlockSpec((lyr, 1, page, kvh), page_spec)]
        inputs += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # page_table, pos, layer
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rep, width), slot_spec),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(kernel, page=page, n_blocks=n_blocks, scale=scale,
                          window=window, kvh=kvh, rep=rep,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rep, width), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *inputs)
    return out.reshape(b, rep, kvh, d).transpose(0, 2, 1, 3).reshape(b, h, d)
