"""Gather-fused paged flash-decode attention — pages are first-class all the
way into the kernel.

The serve path used to materialize a dense ``(B, S, KVH, D)`` copy of every
slot's pages before running the dense decode kernel, doubling decode HBM
traffic.  Here the page table itself drives the kernel: the table and
per-slot positions are **scalar-prefetched**, and each physical K/V page a
slot reads is DMAed HBM->VMEM directly, its id read from the table — the
paper's "stream KV from HBM into the SDPA pipeline" with no dense
intermediate.

Pool layout: ``([L,] P, page, KVH * D)`` — every KV head of a token side by
side on the lane axis.  The lane-dense last dim is what keeps the pool
unpadded in HBM (a trailing ``(KVH, D=96)`` would pad D to 128 lanes, or
make XLA pick a layout with the page axis minor and copy the whole pool
into the kernel's row-major layout every step).  ``L`` is the layer axis
of a scanned segment's stacked pools, selected by the scalar-prefetched
``layer``.

The online walk (the production path) visits only each slot's live
blocks.  From the scalar-prefetched ``pos`` and the static ``window`` a
slot's live blocks are ``lo .. pos // page``, with ``lo`` 0 for full
attention and ``(pos - window + 1) // page`` (at least 0) for a sliding
window, so the ring pages reclaimed behind the window are never visited.
``live_walk`` is the one statement of that range, shared with the engine's
page counters.  The walk takes one of two forms, by the pools' width
(``pages_per_step``):

  * the chunk walk, for a ``KVH * D`` of whole 128-lane tiles: the grid
    is ``(B,)``, one step per slot, and an in-kernel loop walks the live
    blocks in chunks of ``ppb`` pages (``pages_per_chunk``: the largest
    power of two whose K chunk fits ``CHUNK_BYTES``, and no more than a
    slot can have live).  The K/V pools stay in HBM, and each live page of
    a chunk is gathered into a ``(ppb, page, KVH * D)`` VMEM buffer by its
    own async copy, its page id read from the scalar-prefetched table.
    The buffers are doubled, so chunk ``c + 1`` (or the next slot's first
    chunk) loads while chunk ``c`` folds.  Only the rows of the last chunk
    past ``pos`` are computed and masked.
  * the page walk, for any other width, which the TPU compiler does not
    let a DMA slice out of an HBM pool: the grid is ``(B, steps)``, one
    page a step through the BlockSpec pipeline, where ``steps`` is the
    most blocks a slot can have live (the table's ``n_blocks``, or
    ``ceil(window / page) + 1`` inside a window).  Step ``j`` reads block
    ``lo + j``; the steps past a slot's last live block repeat its page,
    so they cost a grid step but neither a DMA nor FLOPs.

Either way the blocks outside the live range are never read.

Each chunk (or page) folds as one ``(rows, KVH * D)`` block.  The per-head
contractions stay lane-dense: ``k * q`` summed per head through a 0/1
``(KVH * D, KVH)`` segment matrix gives the ``(rows, KVH)`` scores, and
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

  * ``accum="online"`` — classic flash-decode over the live walk: fp32
    (m, l, acc) running state in VMEM scratch, rescaled per chunk or
    page.  O(1) scratch in sequence length; the production TPU path.
  * ``accum="exact"``  — a ``(B, n_blocks)`` grid over the whole table,
    one page a step: K and V pages are staged into position-ordered VMEM
    scratch, and the final grid step runs scores, softmax and the P·V
    contraction as single ops, reproducing the oracle's op sequence
    **bit-exactly** (verified in CI against ``paged_decode_attention_ref``
    in interpret mode).  Scratch is O(S_max · KVH · D) per slot — the CPU
    verification mode (it is not sized for the chip's VMEM, and its
    per-head reshapes are not TPU layouts), and the numerics contract the
    online mode is tested against.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_EXACT = jax.lax.Precision.HIGHEST
# the most K bytes one chunk of the online walk gathers into VMEM
CHUNK_BYTES = 512 * 1024
# a DMA slices a page out of an HBM pool only along whole lane tiles
LANES = 128


def max_live_blocks(page: int, n_blocks: int, window) -> int:
    """The most blocks a slot can have live: the table's ``n_blocks``, or
    ``ceil(window / page) + 1`` inside a window."""
    return n_blocks if window is None else min(n_blocks,
                                               -(-window // page) + 1)


def pages_per_chunk(page: int, row_bytes: int, n_blocks: int, window) -> int:
    """Pages a chunk of the online walk gathers: the largest power of two
    whose K chunk (``page`` tokens of ``row_bytes`` each) fits
    ``CHUNK_BYTES``, and no more than a slot can have live (its table's
    ``n_blocks``, or ``ceil(window / page) + 1`` inside a window)."""
    fit = max(1, CHUNK_BYTES // (page * row_bytes))
    return min(max_live_blocks(page, n_blocks, window),
               1 << (fit.bit_length() - 1))


def pages_per_step(page: int, width: int, itemsize: int, n_blocks: int,
                   window) -> int:
    """Pages the online walk folds at a time for ``width``-lane pools of
    ``itemsize``-byte codes: a chunk of ``pages_per_chunk`` on the chunk
    walk, which a width of whole ``LANES`` tiles takes; one on the page
    walk, which any other width takes."""
    if width % LANES:
        return 1
    return pages_per_chunk(page, width * itemsize, n_blocks, window)


def live_walk(pos, page: int, window, ppb: int, xp=np):
    """The online walk of the token at ``pos``: its first live block, how
    many live blocks follow from there (through ``pos // page``), and the
    chunks of ``ppb`` pages that cover them.  Elementwise over host arrays
    (``xp=np``) as over the kernel's scalars (``xp=jnp``)."""
    hi = pos // page
    lo = 0 * hi if window is None else xp.maximum((pos - window + 1) // page,
                                                  0)
    live = hi - lo + 1
    return lo, live, (live + ppb - 1) // ppb


def _visible(idx, pos, window):
    """Which absolute positions ``idx`` the token at ``pos`` attends to."""
    valid = idx <= pos
    if window is not None:
        valid = valid & (idx > pos - window)
    return valid


def _head_segments(kvh: int, d: int, transpose: bool = False):
    """0/1 f32 matrix mapping lane ``w`` to KV head ``w // d``:
    ``(kvh * d, kvh)``, or ``(kvh, kvh * d)`` when ``transpose``."""
    shape, lane_axis = ((kvh, kvh * d), 1) if transpose else ((kvh * d, kvh), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, lane_axis)
    head = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - lane_axis)
    return ((lane >= head * d) & (lane < head * d + d)).astype(jnp.float32)


def _scale_rows(scales, layer, page: int, kvh: int):
    """One layer's ``(P, page, KVH)`` scales as ``(1, P, 1, lanes)``: each
    page's scales on one row, token-major (lane ``t * KVH + h``), padded
    to a multiple of 128 lanes.  A DMA slices a page out of an HBM array
    only along a 128-aligned last dim, which ``KVH`` lanes are not."""
    s = scales[layer]
    n = page * kvh
    s = s.reshape(s.shape[0], 1, n)
    return jnp.pad(s, ((0, 0), (0, 0), (0, -n % 128)))[None]


def _token_scales(rows, page: int, kvh: int):
    """``(ppb, 1, lanes)`` page rows of ``_scale_rows`` -> the ``(ppb *
    page, KVH)`` per-token scales, through a 0/1 lane-to-head matrix."""
    ppb, _, lanes = rows.shape
    shape = (ppb, page, lanes)
    tok = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    mine = (lane >= tok * kvh) & (lane < tok * kvh + kvh)   # token tok's
    x = jnp.where(mine, jnp.broadcast_to(rows, shape), 0.0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (lanes, kvh), 0)
    head = jax.lax.broadcasted_iota(jnp.int32, (lanes, kvh), 1)
    pick = (lane % kvh == head).astype(jnp.float32)
    return jnp.dot(x.reshape(ppb * page, lanes), pick, precision=_EXACT)


def _fold(k, v, first, pos, q_ref, m_ref, l_ref, acc_ref, seg, seg_t, *,
          scale: float, window, rep: int):
    """Fold f32 ``(rows, KVH * D)`` K and V rows, at absolute positions
    ``first ..``, into the running (m, l, acc) of each of the ``rep`` query
    rows; the rows the token at ``pos`` does not see are masked.  ``seg``
    and ``seg_t``: the ``_head_segments`` matrices."""
    idx = first + jax.lax.broadcasted_iota(jnp.int32, (k.shape[0], 1), 0)
    visible = _visible(idx, pos, window)                 # (rows, 1)
    for r in range(rep):
        q = q_ref[0, r:r + 1].astype(jnp.float32)        # (1, W)
        s = jnp.dot(k * q, seg, precision=_EXACT) * scale    # (rows, KVH)
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[r:r + 1]                          # (1, KVH)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[r:r + 1] = l_ref[r:r + 1] * corr + jnp.sum(
            p, axis=0, keepdims=True)
        pv = jnp.dot(p, seg_t, precision=_EXACT) * v     # (rows, W)
        acc_ref[r:r + 1] = (
            acc_ref[r:r + 1] * jnp.dot(corr, seg_t, precision=_EXACT)
            + jnp.sum(pv, axis=0, keepdims=True))
        m_ref[r:r + 1] = m_new


def _reset(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _emit(o_ref, l_ref, acc_ref, seg_t):
    """Write the slot's output: the running numerator over its denom."""
    denom = jnp.dot(jnp.maximum(l_ref[...], 1e-30), seg_t,
                    precision=_EXACT)                    # (rep, W)
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _chunk_walk(pt_ref, pos_ref, pools, sem, at_ref, fold, *, page: int,
                ppb: int, window):
    """The chunk walk of grid step ``b``: slot ``b``'s live chunks, each
    live page of a chunk DMAed from its HBM pool into one of two VMEM
    buffers, and ``fold(cur, first)`` run on buffer ``cur`` once its copies
    land (its rows hold absolute positions ``first ..``).  ``pools``:
    ``(hbm pool, buffer, layer)`` triples, copied page by page alike.
    Buffer ``at_ref[0]`` holds (or is loading) the slot's first chunk; the
    last chunk starts the next slot's first."""
    b, n_slots = pl.program_id(0), pl.num_programs(0)

    def walk(bb):
        return live_walk(pos_ref[bb], page, window, ppb, xp=jnp)

    def each_copy(bb, lo, live, c, buf, act):
        """``act`` on the DMA of every live page of slot ``bb``'s chunk
        ``c`` into buffer ``buf`` (all of them signal ``sem[buf]``)."""
        first = lo + c * ppb

        def page_copies(i, carry):
            pid = pt_ref[bb, first + i]
            for src, dst, ly in pools:
                act(pltpu.make_async_copy(src.at[ly, pid], dst.at[buf, i],
                                          sem.at[buf]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, live - c * ppb), page_copies, 0)

    def start(*chunk):
        each_copy(*chunk, act=lambda cp: cp.start())

    def wait(*chunk):
        each_copy(*chunk, act=lambda cp: cp.wait())

    @pl.when(b == 0)
    def _first():
        # the last chunk's rows past the live pages keep what the buffer
        # held: zero it once, so that is only ever finite
        for _, buf_ref, _ in pools:
            buf_ref[...] = jnp.zeros(buf_ref.shape, buf_ref.dtype)
        at_ref[0] = 0
        start(0, *walk(0)[:2], 0, 0)

    lo, live, n_chunks = walk(b)
    nxt_lo, nxt_live, _ = walk(jnp.minimum(b + 1, n_slots - 1))
    at = at_ref[0]

    def body(c, carry):
        cur = (at + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _next_chunk():
            start(b, lo, live, c + 1, 1 - cur)

        @pl.when((c + 1 == n_chunks) & (b + 1 < n_slots))
        def _next_slot():
            start(b + 1, nxt_lo, nxt_live, 0, 1 - cur)

        wait(b, lo, live, c, cur)
        fold(cur, (lo + c * ppb) * page)
        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)
    at_ref[0] = (at + n_chunks) % 2


def _chunk_walk_kernel(pt_ref, pos_ref, layer_ref, q_ref, k_hbm, v_hbm,
                       *rest, page: int, ppb: int, scale: float, window,
                       kvh: int, rep: int, quantized: bool = False):
    """Grid step ``b``: fold slot ``b``'s live chunks (``_chunk_walk``)
    into (m, l, acc) and write its output."""
    layer = layer_ref[0]
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem, at_ref,
         m_ref, l_ref, acc_ref) = rest
        # the scale rows are this one layer's (see _scale_rows)
        pools = ((k_hbm, k_buf, layer), (v_hbm, v_buf, layer),
                 (ks_hbm, ks_buf, 0), (vs_hbm, vs_buf, 0))
    else:
        o_ref, k_buf, v_buf, sem, at_ref, m_ref, l_ref, acc_ref = rest
        pools = ((k_hbm, k_buf, layer), (v_hbm, v_buf, layer))
    d = q_ref.shape[-1] // kvh
    rows = ppb * page
    pos = pos_ref[pl.program_id(0)]
    _reset(m_ref, l_ref, acc_ref)
    seg = _head_segments(kvh, d)                         # (W, KVH)
    seg_t = _head_segments(kvh, d, transpose=True)       # (KVH, W)

    def fold(cur, first):
        k = k_buf[cur].astype(jnp.float32).reshape(rows, -1)   # (rows, W)
        v = v_buf[cur].astype(jnp.float32).reshape(rows, -1)
        if quantized:
            # dequant fused into the page walk: each token's per-head f32
            # scale spread over that head's lanes
            k = k * jnp.dot(_token_scales(ks_buf[cur], page, kvh), seg_t,
                            precision=_EXACT)
            v = v * jnp.dot(_token_scales(vs_buf[cur], page, kvh), seg_t,
                            precision=_EXACT)
        _fold(k, v, first, pos, q_ref, m_ref, l_ref, acc_ref, seg, seg_t,
              scale=scale, window=window, rep=rep)

    _chunk_walk(pt_ref, pos_ref, pools, sem, at_ref, fold, page=page,
                ppb=ppb, window=window)
    _emit(o_ref, l_ref, acc_ref, seg_t)


def _page_walk_kernel(pt_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
                      page: int, scale: float, window, kvh: int, rep: int,
                      quantized: bool = False):
    """Grid step ``(b, j)``: fold slot ``b``'s live block ``lo + j`` (the
    page the BlockSpec pipeline brought), if it has one; write the output
    at the last step."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    lo, live, _ = live_walk(pos, page, window, 1, xp=jnp)
    d = q_ref.shape[-1] // kvh

    @pl.when(j == 0)
    def _init():
        _reset(m_ref, l_ref, acc_ref)

    @pl.when(j < live)
    def _fold_page():
        seg_t = _head_segments(kvh, d, transpose=True)
        k = k_ref[0].astype(jnp.float32)                 # (page, W)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # dequant fused into the page walk
            k = k * jnp.dot(ks_ref[0], seg_t, precision=_EXACT)
            v = v * jnp.dot(vs_ref[0], seg_t, precision=_EXACT)
        _fold(k, v, (lo + j) * page, pos, q_ref, m_ref, l_ref, acc_ref,
              _head_segments(kvh, d), seg_t, scale=scale, window=window,
              rep=rep)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        _emit(o_ref, l_ref, acc_ref, _head_segments(kvh, d, transpose=True))


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
    inputs = [qg, k_pages, v_pages]
    if quantized:
        inputs += [k_scales, v_scales]
    statics = dict(page=page, scale=scale, window=window, kvh=kvh, rep=rep,
                   quantized=quantized)
    slot_spec = lambda bb, *_: (bb, 0, 0)
    if accum == "online" and width % LANES == 0:
        ppb = pages_per_chunk(page, width * k_pages.dtype.itemsize, n_blocks,
                              window)
        buffers = [pltpu.VMEM((2, ppb, page, width), a.dtype)
                   for a in inputs[1:3]]
        if quantized:
            inputs[3:] = [_scale_rows(a, layer, page, kvh)
                          for a in inputs[3:]]
            buffers += [pltpu.VMEM((2, ppb) + a.shape[2:], a.dtype)
                        for a in inputs[3:]]
        hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                 # page_table, pos, layer
            grid=(b,),
            in_specs=[pl.BlockSpec((1, rep, width), slot_spec)]
            + [hbm] * (len(inputs) - 1),
            out_specs=pl.BlockSpec((1, rep, width), slot_spec),
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((2,)),     # one per buffer
                pltpu.SMEM((1,), jnp.int32),       # the next slot's buffer
                pltpu.VMEM((rep, kvh), jnp.float32),     # running max
                pltpu.VMEM((rep, kvh), jnp.float32),     # running denom
                pltpu.VMEM((rep, width), jnp.float32),   # running numerator
            ],
        )
        kernel = functools.partial(_chunk_walk_kernel, ppb=ppb, **statics)
        # a slot's last chunk starts the next slot's first: steps in order
        params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    elif accum in ("online", "exact"):
        if accum == "online":
            steps = max_live_blocks(page, n_blocks, window)

            def block(bb, j, ps):          # live block lo + j, or the last
                lo, live, _ = live_walk(ps[bb], page, window, 1, xp=jnp)
                return lo + jnp.minimum(j, live - 1)

            scratch = [
                pltpu.VMEM((rep, kvh), jnp.float32),     # running max
                pltpu.VMEM((rep, kvh), jnp.float32),     # running denom
                pltpu.VMEM((rep, width), jnp.float32),   # running numerator
            ]
            kernel = functools.partial(_page_walk_kernel, **statics)
        else:
            steps, block = n_blocks, lambda bb, j, ps: j
            scratch = [
                pltpu.VMEM((n_blocks * page, kvh, d), jnp.float32),  # K
                pltpu.VMEM((n_blocks * page, kvh, d), jnp.float32),  # V
            ]
            kernel = functools.partial(_exact_kernel, n_blocks=n_blocks,
                                       **statics)
        # one block = one whole physical page of one layer, all KV heads:
        # its last two dims equal the pool's, which is what the TPU
        # lowering requires of a block narrower than (8, 128)
        lyr = pl.Squeezed()
        page_spec = lambda bb, j, pt, ps, ly: (ly[0], pt[bb, block(bb, j, ps)],
                                               0, 0)
        in_specs = [
            pl.BlockSpec((1, rep, width), slot_spec),
            pl.BlockSpec((lyr, 1, page, width), page_spec),
            pl.BlockSpec((lyr, 1, page, width), page_spec),
        ]
        if quantized:
            # scale pages ride the same page-table-driven index map
            in_specs += [pl.BlockSpec((lyr, 1, page, kvh), page_spec)] * 2
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                 # page_table, pos, layer
            grid=(b, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, rep, width), slot_spec),
            scratch_shapes=scratch,
        )
        params = None
    else:
        raise ValueError(f"accum={accum!r} (want 'online' or 'exact')")
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rep, width), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *inputs)
    return out.reshape(b, rep, kvh, d).transpose(0, 2, 1, 3).reshape(b, h, d)
