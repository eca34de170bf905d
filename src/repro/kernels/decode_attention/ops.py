"""Public op wrappers for the decode-attention kernel (dense and paged)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import on_cpu
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.paged_kernel import paged_decode_attention
from repro.kernels.decode_attention.paged_mla_kernel import paged_mla_decode
from repro.kernels.decode_attention.ref import (
    decode_attention_ref, gather_pages, paged_decode_attention_ref,
    paged_decode_multi_attention_ref, paged_mla_decode_ref,
)


def gqa_decode_attention(q, k_cache, v_cache, cur_len, *, block_s: int = 512):
    """(B,H,D) x (B,S,KVH,D) cache -> (B,H,D); kernel when tiles fit,
    jnp oracle otherwise (tiny smoke shapes / ragged S)."""
    s = k_cache.shape[1]
    bs = min(block_s, s)
    if s % bs != 0 or q.shape[1] % k_cache.shape[2] != 0:
        return decode_attention_ref(q, k_cache, v_cache, cur_len)
    return decode_attention(q, k_cache, v_cache, cur_len, block_s=bs,
                            interpret=on_cpu())


def _oracle_pools(layer, d, k_pages, v_pages, k_scales, v_scales):
    """The oracles' view of serve-layout pools: one layer (of layer-stacked
    pools) with the lane-dense ``KVH * D`` axis split into ``(KVH, D)``."""
    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
        if k_scales is not None:
            k_scales, v_scales = k_scales[layer], v_scales[layer]
    split = lambda p: p.reshape(p.shape[:-1] + (p.shape[-1] // d, d))
    return split(k_pages), split(v_pages), k_scales, v_scales


def paged_gqa_multi_attention(q, k_pages, v_pages, page_table, start, *,
                              layer=None, k_scales=None, v_scales=None,
                              causal=True, window=None, impl: str = "auto"):
    """Multi-token paged attention: the q_len > 1 counterpart of
    ``paged_gqa_decode_attention``, used by chunked prefill and the
    speculative verify step (q_len = gamma + 1).

    q:          (B, C, H, D) — C queries per slot at per-row absolute
                offsets ``start`` (query j of row b sits at position
                start[b] + j and attends causally up to itself)
    k_pages / v_pages / page_table / layer / k_scales / v_scales: as in
                ``paged_gqa_decode_attention`` (serve-layout pools)

    Impls (no separate kernel either way — the gather-fused Pallas path
    only covers q_len == 1 today; multi-token flash-decode over
    scalar-prefetched pages is a recorded follow-on):

      * ``"blocked"``   — gather pages, dequantize, hand to
        ``blocked_attention``'s ragged ``q_offset`` online-softmax path.
        What chunked prefill has always used.
      * ``"reference"`` — ``paged_decode_multi_attention_ref``, op-for-op
        the single-token decode oracle per query.  The speculative verify
        step needs THIS on CPU: its per-position logits are bit-identical
        to the non-speculative decode step's, which is what makes greedy
        speculation byte-identical end to end (the blocked online softmax
        differs at ulp scale — enough to flip argmax on near-ties).
      * ``"auto"``      — reference on CPU (the byte-exactness contract
        lives there), blocked on accelerators (where single-token decode
        takes the fused online-softmax kernel anyway).
    """
    if impl == "auto":
        impl = "reference" if on_cpu() else "blocked"
    if impl == "reference":
        assert causal, "the multi-token decode oracle is causal-only"
        k_pages, v_pages, k_scales, v_scales = _oracle_pools(
            layer, q.shape[-1], k_pages, v_pages, k_scales, v_scales)
        return paged_decode_multi_attention_ref(
            q, k_pages, v_pages, page_table, start, k_scales=k_scales,
            v_scales=v_scales, window=window)
    if impl != "blocked":
        raise ValueError(f"impl={impl!r} (want 'auto', 'blocked' or "
                         "'reference')")
    from repro.quant import kv as kvq
    d = q.shape[-1]
    k_d = gather_pages(k_pages, page_table, layer)       # (B, S, KVH * D)
    v_d = gather_pages(v_pages, page_table, layer)
    k_d = k_d.reshape(k_d.shape[:2] + (-1, d))
    v_d = v_d.reshape(v_d.shape[:2] + (-1, d))
    if k_scales is not None:
        k_d = kvq.kv_dequantize(k_d, gather_pages(k_scales, page_table, layer),
                                q.dtype)
        v_d = kvq.kv_dequantize(v_d, gather_pages(v_scales, page_table, layer),
                                q.dtype)
    from repro.models.common import blocked_attention
    return blocked_attention(q, k_d, v_d, causal=causal, window=window,
                             q_offset=start)


def paged_gqa_decode_attention(q, k_pages, v_pages, page_table, pos, *,
                               layer=None, k_scales=None, v_scales=None,
                               window=None, impl: str = "auto"):
    """Paged single-token decode attention behind one of two impls.

    Pools are in the serve layout ``([L,] P, page, KVH * D)`` (see
    ``paged_kernel``; scales ``([L,] P, page, KVH)``).  ``layer`` (a traced
    int32 scalar) picks one layer of layer-stacked ``(L, P, page, ...)``
    pools — how the scanned decode step passes its pools, so they are
    updated in place rather than re-stacked; None means the pools are a
    single layer's ``(P, page, ...)``.

      * ``"fused"``     — the gather-fused Pallas kernel: the page table
        drives the grid, each K/V page streams HBM->VMEM straight into the
        flash-decode accumulator.  No dense ``(B, S, KVH, D)`` intermediate.
      * ``"reference"`` — gather-then-dense jnp oracle; the bit-exact
        counterpart of the dense serve path.

    ``"auto"`` takes the oracle on CPU (where the fused kernel would run in
    slow interpret mode, and token-exactness with the dense engine is the
    test contract) and the fused kernel on accelerators.  Tests exercise
    the fused kernel on CPU explicitly via ``impl="fused"`` +
    ``interpret=True`` inside ``paged_decode_attention``.
    """
    if impl == "auto":
        impl = "reference" if on_cpu() else "fused"
    if impl == "reference":
        k_pages, v_pages, k_scales, v_scales = _oracle_pools(
            layer, q.shape[-1], k_pages, v_pages, k_scales, v_scales)
        return paged_decode_attention_ref(q, k_pages, v_pages, page_table,
                                          pos, k_scales=k_scales,
                                          v_scales=v_scales, window=window)
    if impl != "fused":
        raise ValueError(f"impl={impl!r} (want 'auto', 'fused' or 'reference')")
    return paged_decode_attention(q, k_pages, v_pages, page_table,
                                  pos.astype(jnp.int32), layer=layer,
                                  k_scales=k_scales, v_scales=v_scales,
                                  window=window, interpret=on_cpu())


def paged_mla_decode_attention(q, pages, page_table, pos, *, rank: int,
                               scale: float, layer=None, impl: str = "auto"):
    """Paged single-token latent (MLA) decode: absorbed queries ``q`` (B, H,
    W) over the ``([L,] P, page, W)`` latent pool -> the f32 (B, H, rank)
    context.  ``"fused"``: the ``paged_mla_decode`` Pallas kernel (live
    pages only, each read once for every head); ``"reference"``: the
    gather-then-dense oracle.  ``"auto"`` takes the oracle on CPU and the
    kernel on accelerators, as ``paged_gqa_decode_attention`` does."""
    if impl == "auto":
        impl = "reference" if on_cpu() else "fused"
    if impl == "reference":
        return paged_mla_decode_ref(q, pages, page_table, pos, rank=rank,
                                    scale=scale, layer=layer)
    if impl != "fused":
        raise ValueError(f"impl={impl!r} (want 'auto', 'fused' or 'reference')")
    return paged_mla_decode(q, pages, page_table, pos.astype(jnp.int32),
                            rank=rank, scale=scale, layer=layer,
                            interpret=on_cpu())
