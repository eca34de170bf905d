"""Pure-jnp oracles for flash-decode GQA attention (dense and paged)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import NEG_INF, decode_attention_ref  # noqa: F401


def gather_pages(pages: jnp.ndarray, page_table: jnp.ndarray,
                 layer=None) -> jnp.ndarray:
    """(P, page, ...) pool + (B, n_blocks) table -> (B, n_blocks*page, ...)
    position-ordered dense view (block i of row b = physical page
    ``page_table[b, i]``).  With ``layer``, ``pages`` is a layer-stacked
    ``(L, P, page, ...)`` pool and the view is of layer ``layer``."""
    g = pages[page_table] if layer is None else pages[layer, page_table]
    b, nb, ps = g.shape[:3]
    return g.reshape((b, nb * ps) + g.shape[3:])


def paged_valid_mask(page_table: jnp.ndarray, page_size: int,
                     pos: jnp.ndarray, *, window=None) -> jnp.ndarray:
    """(B, n_blocks*page) bool mask of logical positions visible to the
    token being decoded at per-row position ``pos`` (inclusive: the new
    token's own k/v has already been scattered at ``pos``)."""
    s = page_table.shape[1] * page_size
    idx = jnp.arange(s)[None, :]
    valid = idx <= pos[:, None]
    if window is not None:
        valid = valid & (idx > pos[:, None] - window)
    return valid


def paged_decode_multi_attention_ref(q, k_pages, v_pages, page_table, start,
                                     *, k_scales=None, v_scales=None,
                                     window=None, scale=None):
    """Multi-token paged decode oracle: C queries per slot at per-row
    offsets (speculative verify, q_len = gamma + 1).

    q: (B, C, H, D); start: (B,) absolute position of q[:, 0]; query j of
    row b sits at position start[b] + j and sees keys <= its own position.

    Op-for-op the same computation as ``paged_decode_attention_ref`` per
    query (gather -> dequant -> matmul -> mask -> softmax -> matmul, f32
    softmax), so each position's logits are bit-identical to what the
    single-token decode path produces for the same pool state — the
    greedy byte-identity contract between the speculative and
    non-speculative continuous engines rests on this.
    """
    b, c, h, d = q.shape
    kvh = k_pages.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    rep = h // kvh
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    if k_scales is not None:
        k = k.astype(jnp.float32) * gather_pages(k_scales, page_table)[..., None]
        v = v.astype(jnp.float32) * gather_pages(v_scales, page_table)[..., None]
    s_len = k.shape[1]
    pos = start[:, None] + jnp.arange(c)[None, :]          # (B, C)
    idx = jnp.arange(s_len)
    valid = idx[None, None, :] <= pos[:, :, None]          # (B, C, S)
    if window is not None:
        valid = valid & (idx[None, None, :] > pos[:, :, None] - window)
    qf = q.astype(jnp.float32).reshape(b, c, kvh, rep, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bcgrd,bsgd->bcgrs", qf, kf) * scale
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bcgrs,bsgd->bcgrd", p, vf)
    return out.reshape(b, c, h, vf.shape[-1]).astype(q.dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, pos, *,
                               k_scales=None, v_scales=None,
                               window=None, scale=None):
    """Paged single-token decode attention oracle.

    q:          (B, H, D) — one new token per slot
    k_pages:    (P, page, KVH, D) physical page pool
    v_pages:    (P, page, KVH, Dv)
    page_table: (B, n_blocks) int32 — logical block -> physical page
    pos:        (B,) int32 — per-slot position of the new token
    k_scales/v_scales: (P, page, KVH) f32 per-token dequant scales for
                fp8/int8 code pools (None = dense pools)

    Gathers pages into a position-ordered dense view and reuses the dense
    oracle, so paged-vs-dense equivalence is exact by construction.  The
    dequant (f32 cast then one multiply per element) mirrors the fused
    kernel's in-loop dequant op-for-op, keeping the bit-exact contract.
    """
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    if k_scales is not None:
        k = k.astype(jnp.float32) * gather_pages(k_scales, page_table)[..., None]
        v = v.astype(jnp.float32) * gather_pages(v_scales, page_table)[..., None]
    valid = paged_valid_mask(page_table, k_pages.shape[1], pos, window=window)
    return decode_attention_ref(q, k, v, None, valid=valid, scale=scale)


def paged_mla_decode_ref(q, pages, page_table, pos, *, rank: int,
                         scale: float, layer=None):
    """Gather-then-dense oracle of ``paged_mla_decode``: every head's f32
    ``(B, H, rank)`` latent context over its slot's rows up to ``pos``.
    ``q``: (B, H, W) absorbed queries against the ``(.., page, W)`` latent
    rows (lanes past ``rank`` hold the rotary key, then zeros)."""
    lat = gather_pages(pages, page_table, layer).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), lat) * scale
    valid = paged_valid_mask(page_table, pages.shape[-2], pos)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p, lat[..., :rank])
