"""Attention-backend registry: the single seam between block kinds and
attention implementations.

Each backend bundles, for one attention family, everything the model
assembly and the serving runtime need to know:

  * parameter / cache / page-pool constructors (the **cache layout**);
  * the dense apply paths (forward / prefill / decode);
  * the paged serve paths (single-token ``decode_paged`` against the page
    pools, and ``prefill_chunk_paged`` for chunked admission);
  * the **mask families** each path supports (``"prefix"`` — causal over
    the whole cache — and/or ``"sliding"``).

``model.py`` dispatches every block through ``backend_for_kind`` instead of
string-prefix branching, and ``runtime/engine.py`` stays entirely
layout-agnostic (pools are opaque pytrees whose leaves all carry a leading
page axis).  Adding a paged layout for a new family — ring pages for SWA,
SSM state admission — means registering a backend, not editing the engine.

The paged decode kernels behind the GQA backend live in
``kernels/decode_attention`` (gather-fused Pallas kernel on accelerators,
gather-then-dense oracle on CPU); MLA's absorbed-matmul latent decode
streams its latent pages through ``paged_mla_decode`` the same way.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.common import NEG_INF, ModelConfig, blocked_attention
from repro.models.ssm import init_ssm_state
from repro.kernels.decode_attention.paged_mla_kernel import latent_lanes
from repro.kernels.decode_attention.ref import gather_pages
from repro.parallel.hints import tp_row_dot
from repro.quant import kv as kvq


# ---------------------------------------------------------------------------
# Backend descriptor + registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionBackend:
    """One attention family's implementations and cache layout."""
    name: str
    paged_leaf_keys: tuple[str, ...]        # pool leaves with a token axis
    mask_families: tuple[str, ...]          # dense paths
    paged_mask_families: tuple[str, ...]    # paged paths
    init: Callable[..., dict]
    init_cache: Callable[..., dict]
    forward: Callable[..., Any]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]
    init_page_pool: Callable[..., dict] | None = None
    decode_paged: Callable[..., Any] | None = None
    prefill_chunk_paged: Callable[..., Any] | None = None
    # Multi-token decode (speculative verify): C queries per slot at
    # per-row offsets, scatter-then-attend over the paged pools with
    # ``blocked_attention``'s ragged q_offset machinery — the same
    # contract as prefill_chunk_paged (start, valid), and for both
    # built-in families literally the same body: a verify window IS a
    # chunk of already-chosen tokens whose logits we keep at every
    # position instead of just the last one (that difference lives in
    # ``Model.decode_step_paged``, not here).
    decode_multi_paged: Callable[..., Any] | None = None
    # Tensor-parallel partition of the page pools (sharded paged serving):
    # leaf key -> the UNSTACKED pool-leaf dim that shards over the mesh's
    # model axis, or None for a replicated leaf.  GQA pools shard their
    # KV-head axis (each shard streams only its local head slice — the
    # paper's "KV$ sharded across CUs"); MLA's latent pools are shared by
    # every head and stay replicated.  ``parallel.plan.PagedServePlan``
    # turns this into shard_map specs / NamedShardings, so new families
    # (ssm state pools, ring pages) declare their sharding here instead of
    # hard-coding it in the engine.
    paged_partition_spec: dict[str, int | None] | None = None

    @property
    def supports_paged(self) -> bool:
        return self.init_page_pool is not None


_REGISTRY: dict[str, AttentionBackend] = {}

# block kind -> backend name; kinds without attention (ssm) map to None
KIND_BACKEND: dict[str, str | None] = {
    "attn_dense": "gqa",
    "attn_moe": "gqa",
    "hybrid": "gqa",          # the attention half; SSM state is separate
    "mla_dense": "mla",
    "mla_moe": "mla",
    "ssm": None,
}


def register_backend(backend: AttentionBackend) -> AttentionBackend:
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> AttentionBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown attention backend {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def backend_for_kind(kind: str) -> AttentionBackend | None:
    try:
        name = KIND_BACKEND[kind]
    except KeyError:
        raise ValueError(f"unknown block kind {kind!r}") from None
    return get_backend(name) if name else None


# ---------------------------------------------------------------------------
# Cache layouts: what a block kind keeps resident per serving slot
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """Per-slot cache residency contract for one block kind.

    The attention backends above describe *how* a family computes; the
    cache layout describes *what it keeps resident* while serving — the
    axis ``DeploymentSpec.resolve`` budgets and ``runtime.state_cache``
    allocates:

      * ``kv``     — the kind writes token-indexed pages (full-context for
        prefix layers, ring-reclaimed O(window) for sliding-window layers;
        which of the two is a property of the segment's window, not the
        kind, so it lives in ``runtime.state_cache.SegmentCacheLayout``);
      * ``state``  — the kind carries constant-size recurrent state (SSM
        conv tail + SSD state), pooled per slot by the engine and stepped
        via ``ssm_decode_step``;
      * ``init_state_pool`` — constructor for the slot-indexed state
        pytree, ``(cfg, num_slots) -> pytree``, leading axis = slot;
      * ``state_partition_spec`` — leaf key -> UNSTACKED state-leaf dim
        sharded over the mesh's model axis (None = replicated), mirroring
        ``AttentionBackend.paged_partition_spec`` for state pools.
    """
    kv: bool
    state: bool
    init_state_pool: Callable[..., dict] | None = None
    state_partition_spec: dict[str, int | None] | None = None


_SSM_STATE_LAYOUT = dict(
    state=True,
    init_state_pool=lambda cfg, num_slots: init_ssm_state(cfg, num_slots),
    # conv (slot, K-1, conv_dim) and ssm (slot, H, P, N) state replicates
    # across the TP ring today (sharded stateful serving is gated in
    # ``parallel.plan.make_paged_serve_plan``); the seam is declared here
    # so lifting that gate means editing specs, not the engine.
    state_partition_spec={"conv": None, "ssm": None},
)

# block kind -> residency layout.  Attention kinds are pure-KV; ssm is
# pure-state; hybrid blocks own both a KV half and a state half in the
# SAME slot (admission/eviction moves them together).
KIND_LAYOUT: dict[str, CacheLayout] = {
    "attn_dense": CacheLayout(kv=True, state=False),
    "attn_moe": CacheLayout(kv=True, state=False),
    "mla_dense": CacheLayout(kv=True, state=False),
    "mla_moe": CacheLayout(kv=True, state=False),
    "hybrid": CacheLayout(kv=True, **_SSM_STATE_LAYOUT),
    "ssm": CacheLayout(kv=False, **_SSM_STATE_LAYOUT),
}


def layout_for_kind(kind: str) -> CacheLayout:
    try:
        return KIND_LAYOUT[kind]
    except KeyError:
        raise ValueError(f"unknown block kind {kind!r}") from None


# ---------------------------------------------------------------------------
# Paged helpers shared by the backends
# ---------------------------------------------------------------------------


# ``layer`` (every paged path below takes it): None when ``pool`` is one
# layer's ``(P, page, ...)`` leaves; the layer index when the leaves are
# layer-stacked ``(L, P, page, ...)`` — how scanned segments carry their
# pools, so each step writes and reads them in place instead of
# re-stacking a copy of every layer's pool.


def _token_index(layer, phys, off):
    return (phys, off) if layer is None else (layer, phys, off)


def scatter_token(pool_leaf: jnp.ndarray, vals: jnp.ndarray, page_table,
                  pos, layer=None) -> jnp.ndarray:
    """Scatter one token per slot: vals (B, ...) at per-slot position pos."""
    b = vals.shape[0]
    page = pool_leaf.shape[1 if layer is None else 2]
    blk, off = pos // page, pos % page
    phys = page_table[jnp.arange(b), blk]
    return pool_leaf.at[_token_index(layer, phys, off)].set(
        vals.astype(pool_leaf.dtype))


def scatter_chunk(pool_leaf: jnp.ndarray, vals: jnp.ndarray, page_table,
                  positions, ok, layer=None) -> jnp.ndarray:
    """Scatter a chunk of tokens per slot through the page table.

    vals: (B, C, ...); positions: (B, C) absolute; ok: (B, C) — entries with
    ``ok=False`` (padding rows / the tail of a short last chunk) are
    redirected to the scratch page so live pages are never corrupted."""
    b, c = positions.shape
    page = pool_leaf.shape[1 if layer is None else 2]
    okf = ok.reshape(-1)
    pos_f = jnp.where(okf, positions.reshape(-1), 0)
    bidx = jnp.repeat(jnp.arange(b), c)
    phys = jnp.where(okf, page_table[bidx, pos_f // page], 0)
    off = jnp.where(okf, pos_f % page, 0)
    flat = vals.reshape((b * c,) + vals.shape[2:]).astype(pool_leaf.dtype)
    return pool_leaf.at[_token_index(layer, phys, off)].set(flat)


# ---------------------------------------------------------------------------
# GQA backend: paged decode + chunked paged prefill
# ---------------------------------------------------------------------------


def init_attn_page_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                        dtype=jnp.bfloat16) -> dict:
    """Physical K/V page pool for one layer: ``(P, page, KVH * HD)``, every
    KV head of a token side by side on the (lane-dense, so unpadded on a
    TPU) last axis; see ``kernels.decode_attention.paged_kernel``.

    ``dtype``: bf16 on TPU; CPU serving wants f32 (XLA:CPU re-converts
    bf16 pools to f32 around every gather, doubling the step time).  The
    string dtypes ``"fp8"`` / ``"int8"`` build quantized pools: narrow
    code leaves plus per-token f32 ``k_scale``/``v_scale`` metadata leaves
    of shape ``(P, page, KVH)`` (see ``quant.kv``)."""
    shape = (num_pages, page_size, cfg.n_kv_heads * cfg.hd)
    if kvq.is_quantized_cache_dtype(dtype):
        store = kvq.cache_storage_dtype(dtype)
        scales = (num_pages, page_size, cfg.n_kv_heads)
        return {"k": jnp.zeros(shape, store), "v": jnp.zeros(shape, store),
                "k_scale": jnp.ones(scales, kvq.SCALE_DTYPE),
                "v_scale": jnp.ones(scales, kvq.SCALE_DTYPE)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _scatter_kv_token(pool: dict, k, v, page_table, pos, layer) -> dict:
    """Scatter one token's k/v per slot, quantizing on write for fp8/int8
    pools (scale = amax of the token's head vector, fixed at write time)."""
    fmt = kvq.pool_cache_format(pool)
    vals = {"k": k, "v": v}
    if fmt is not None:
        vals["k"], vals["k_scale"] = kvq.kv_quantize(k, fmt)
        vals["v"], vals["v_scale"] = kvq.kv_quantize(v, fmt)
    for name in ("k", "v"):                  # (B, KVH, HD) -> (B, KVH * HD)
        vals[name] = vals[name].reshape(vals[name].shape[:-2] + (-1,))
    return {name: scatter_token(pool[name], val, page_table, pos, layer)
            for name, val in vals.items()}


def _scatter_kv_chunk(pool: dict, k, v, page_table, positions, ok,
                      layer) -> dict:
    """Chunk analogue of ``_scatter_kv_token`` (k/v: (B, C, KVH, HD))."""
    fmt = kvq.pool_cache_format(pool)
    vals = {"k": k, "v": v}
    if fmt is not None:
        vals["k"], vals["k_scale"] = kvq.kv_quantize(k, fmt)
        vals["v"], vals["v_scale"] = kvq.kv_quantize(v, fmt)
    for name in ("k", "v"):            # (B, C, KVH, HD) -> (B, C, KVH * HD)
        vals[name] = vals[name].reshape(vals[name].shape[:-2] + (-1,))
    return {name: scatter_chunk(pool[name], val, page_table, positions, ok,
                                layer)
            for name, val in vals.items()}


def attn_decode_paged(p: dict, x: jnp.ndarray, cfg: ModelConfig, pool: dict,
                      page_table, pos, *, window=None,
                      layer=None) -> tuple[jnp.ndarray, dict]:
    """One-token step against a paged cache.

    x: (B, D) slot tokens; pos: (B,) int32 per-slot positions (ragged —
    this is the whole point of continuous batching); page_table:
    (B, n_blocks) int32.  The new k/v is scattered into the slot's current
    page before the attention, mirroring the dense write-then-attend order;
    the attention itself streams pages through the gather-fused kernel
    (``impl="auto"``: oracle on CPU, fused Pallas kernel on accelerators).
    Quantized (fp8/int8) pools scatter codes + per-token scales and pass
    the scale pages into the kernel's fused in-loop dequant.
    """
    b, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    positions = pos[:, None]                              # (B, 1) ragged RoPE
    q, k, v = layers._qkv(p, x[:, None, :], cfg, positions)
    new_pool = _scatter_kv_token(pool, k[:, 0], v[:, 0], page_table, pos,
                                 layer)
    from repro.kernels.decode_attention.ops import paged_gqa_decode_attention
    out = paged_gqa_decode_attention(
        q[:, 0], new_pool["k"], new_pool["v"], page_table, pos, layer=layer,
        k_scales=new_pool.get("k_scale"), v_scales=new_pool.get("v_scale"),
        window=window)
    out = tp_row_dot(out.reshape(b, h * hd), p["wo"])
    return out, new_pool


def attn_prefill_chunk_paged(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                             pool: dict, page_table, start, valid, *,
                             window=None,
                             layer=None) -> tuple[jnp.ndarray, dict]:
    """One prefill chunk against the paged cache.

    x: (B, C, D) chunk hidden states; start: (B,) absolute position of
    x[:, 0]; valid: (B,) number of real tokens in the chunk (the rest are
    padding).  The chunk's k/v is scattered into the slot's pages, then the
    chunk queries attend over the gathered view — earlier chunks (and any
    prefix-cache pages shared from another request) are already resident,
    so admission work is proportional to the *unseen* suffix only.
    """
    b, c, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    positions = start[:, None] + jnp.arange(c)[None, :]
    q, k, v = layers._qkv(p, x, cfg, positions)
    ok = jnp.arange(c)[None, :] < valid[:, None]
    new_pool = _scatter_kv_chunk(pool, k, v, page_table, positions, ok,
                                 layer)
    from repro.kernels.decode_attention.ops import paged_gqa_multi_attention
    out = paged_gqa_multi_attention(
        q, new_pool["k"], new_pool["v"], page_table, start, layer=layer,
        k_scales=new_pool.get("k_scale"), v_scales=new_pool.get("v_scale"),
        causal=cfg.causal, window=window, impl="blocked")
    out = tp_row_dot(out.reshape(b, c, h * hd), p["wo"])
    return out, new_pool


def attn_decode_multi_paged(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                            pool: dict, page_table, start, valid, *,
                            window=None,
                            layer=None) -> tuple[jnp.ndarray, dict]:
    """C-token decode step (speculative verify): the tokens are already
    chosen, so this is chunk-shaped scatter-then-attend, but through the
    ``impl="auto"`` multi-query dispatch — bit-matched per position with
    the single-token decode path on CPU (greedy byte-identity), blocked
    online softmax on accelerators."""
    b, c, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    positions = start[:, None] + jnp.arange(c)[None, :]
    q, k, v = layers._qkv(p, x, cfg, positions)
    ok = jnp.arange(c)[None, :] < valid[:, None]
    new_pool = _scatter_kv_chunk(pool, k, v, page_table, positions, ok,
                                 layer)
    from repro.kernels.decode_attention.ops import paged_gqa_multi_attention
    out = paged_gqa_multi_attention(
        q, new_pool["k"], new_pool["v"], page_table, start, layer=layer,
        k_scales=new_pool.get("k_scale"), v_scales=new_pool.get("v_scale"),
        window=window)
    out = tp_row_dot(out.reshape(b, c, h * hd), p["wo"])
    return out, new_pool


# ---------------------------------------------------------------------------
# MLA backend: absorbed-matmul latent decode + chunked paged prefill
# ---------------------------------------------------------------------------


def init_mla_page_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                       dtype=jnp.bfloat16) -> dict:
    """Latent page pool for one MLA layer: ``(P, page, W)`` rows of c_kv,
    then the shared k_rope, then zeros to ``W`` (whole 128-lane tiles; see
    ``kernels.decode_attention.paged_mla_kernel``)."""
    if kvq.is_quantized_cache_dtype(dtype):
        raise NotImplementedError(
            f"cache_dtype={dtype!r} is not implemented for MLA latent page "
            f"pools: the absorbed-matmul decode consumes latent pages "
            f"directly and has no dequant seam yet.  Quantized KV "
            f"({'/'.join(sorted(kvq.KV_FORMATS))}) is only available for "
            f"GQA-family page pools; for MLA models use a dense cache_dtype "
            f"(None, jnp.bfloat16, jnp.float32) instead.")
    width = latent_lanes(cfg.kv_lora_rank, cfg.rope_head_dim)
    return {"latent": jnp.zeros((num_pages, page_size, width), dtype)}


def _latent_rows(c_kv, k_rope, width: int):
    """c_kv (..., r) and k_rope (..., rhd) side by side, zero-padded to the
    pool's ``width`` lanes: latent rows (or the absorbed queries that
    score them)."""
    pad = jnp.zeros(c_kv.shape[:-1] + (width - c_kv.shape[-1]
                                       - k_rope.shape[-1],), c_kv.dtype)
    return jnp.concatenate([c_kv, k_rope.astype(c_kv.dtype), pad], axis=-1)


def _absorbed_queries(p, q_nope, q_rope, cfg: ModelConfig, width: int):
    """f32 (..., H, W) absorbed queries: q_nope through W_uk into the
    latent space, then q_rope, in the latent rows' lane order."""
    h, hd, r = cfg.n_heads, cfg.hd, cfg.kv_lora_rank
    w_uk = p["w_uk"].reshape(r, h, hd).astype(jnp.float32)
    q_lat = jnp.einsum("...hd,rhd->...hr", q_nope.astype(jnp.float32), w_uk)
    return _latent_rows(q_lat, q_rope.astype(jnp.float32), width)


def mla_decode_paged(p, x, cfg: ModelConfig, pool: dict, page_table, pos, *,
                     window=None, layer=None):
    """Absorbed-matmul MLA decode against a paged latent cache.

    Same math as ``layers.mla_decode``, with the latent rows read through
    the page table at per-slot (ragged) positions: on accelerators by the
    ``paged_mla_decode`` kernel, which walks only each slot's live pages.
    """
    assert window is None, "MLA layers are full-attention"
    b, _ = x.shape
    h, vhd, r = cfg.n_heads, cfg.v_hd, cfg.kv_lora_rank
    q_nope, q_rope, c_kv, k_rope = layers._mla_qc(p, x[:, None, :], cfg,
                                                  pos[:, None])
    width = pool["latent"].shape[-1]
    new = scatter_token(pool["latent"],
                        _latent_rows(c_kv[:, 0], k_rope[:, 0], width),
                        page_table, pos, layer)
    from repro.kernels.decode_attention.ops import paged_mla_decode_attention
    ctx = paged_mla_decode_attention(
        _absorbed_queries(p, q_nope[:, 0], q_rope[:, 0], cfg, width), new,
        page_table, pos, rank=r, scale=cfg.mla_softmax_scale, layer=layer)
    w_uv = p["w_uv"].reshape(r, h, vhd)
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv.astype(jnp.float32))
    out = tp_row_dot(out.reshape(b, h * vhd).astype(x.dtype), p["wo"])
    return out, {"latent": new}


def mla_decode_multi_paged(p, x, cfg: ModelConfig, pool: dict, page_table,
                           start, valid, *, window=None, layer=None):
    """C-token absorbed-matmul MLA decode (speculative verify).

    Deliberately mirrors ``mla_decode_paged``'s ABSORBED path — not the
    per-head expansion ``mla_prefill_chunk_paged`` uses — because the
    two associate the latent matmuls differently and diverge at ulp
    scale; verify logits must match the single-token decode path
    bit-for-bit so greedy speculation stays byte-identical."""
    assert window is None, "MLA layers are full-attention"
    b, c, _ = x.shape
    h, vhd, r = cfg.n_heads, cfg.v_hd, cfg.kv_lora_rank
    positions = start[:, None] + jnp.arange(c)[None, :]
    q_nope, q_rope, c_kv, k_rope = layers._mla_qc(p, x, cfg, positions)
    ok = jnp.arange(c)[None, :] < valid[:, None]
    width = pool["latent"].shape[-1]
    new = scatter_chunk(pool["latent"], _latent_rows(c_kv, k_rope, width),
                        page_table, positions, ok, layer)
    lat = gather_pages(new, page_table, layer).astype(jnp.float32)
    q_eff = _absorbed_queries(p, q_nope, q_rope, cfg, width)
    s_ = jnp.einsum("bchw,bsw->bchs", q_eff, lat) * cfg.mla_softmax_scale
    idx = jnp.arange(lat.shape[1])
    vmask = idx[None, None, :] <= positions[:, :, None]    # (B, C, S)
    s_ = jnp.where(vmask[:, :, None, :], s_, NEG_INF)
    pattn = jax.nn.softmax(s_, axis=-1)
    ctx = jnp.einsum("bchs,bsr->bchr", pattn, lat[..., :r])
    w_uv = p["w_uv"].reshape(r, h, vhd)
    out = jnp.einsum("bchr,rhv->bchv", ctx, w_uv.astype(jnp.float32))
    out = tp_row_dot(out.reshape(b, c, h * vhd).astype(x.dtype), p["wo"])
    return out, {"latent": new}


def mla_prefill_chunk_paged(p, x, cfg: ModelConfig, pool: dict, page_table,
                            start, valid, *, window=None, layer=None):
    """One MLA prefill chunk: scatter latents, attend via per-head expansion
    of the gathered latent view (the prefill-style path of ``mla_forward``,
    continued at per-slot offsets)."""
    assert window is None, "MLA layers are full-attention"
    b, c, _ = x.shape
    h, hd, rhd, vhd = cfg.n_heads, cfg.hd, cfg.rope_head_dim, cfg.v_hd
    positions = start[:, None] + jnp.arange(c)[None, :]
    q_nope, q_rope, c_kv, k_rope = layers._mla_qc(p, x, cfg, positions)
    ok = jnp.arange(c)[None, :] < valid[:, None]
    new = scatter_chunk(pool["latent"],
                        _latent_rows(c_kv, k_rope, pool["latent"].shape[-1]),
                        page_table, positions, ok, layer)
    lat = gather_pages(new, page_table, layer)             # (B, S, W)
    r = cfg.kv_lora_rank
    c_d, kr_d = lat[..., :r], lat[..., r:r + rhd]
    s_len = c_d.shape[1]
    k_nope = (c_d @ p["w_uk"]).reshape(b, s_len, h, hd)
    v_d = (c_d @ p["w_uv"]).reshape(b, s_len, h, vhd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr_d[:, :, None, :],
                                                  (b, s_len, h, rhd))], axis=-1)
    out = blocked_attention(q, k, v_d, causal=cfg.causal,
                            scale=cfg.mla_softmax_scale, q_offset=start)
    out = tp_row_dot(out.reshape(b, c, h * vhd), p["wo"])
    return out, {"latent": new}


# ---------------------------------------------------------------------------
# Registration
# ---------------------------------------------------------------------------


GQA = register_backend(AttentionBackend(
    name="gqa",
    paged_leaf_keys=("k", "v"),
    mask_families=("prefix", "sliding"),
    # sliding covers the MASK family only: the fused kernel / oracle skip
    # out-of-window positions, but pages behind the window stay allocated
    # (ring-aware page reclamation is the remaining capacity half).
    paged_mask_families=("prefix", "sliding"),
    init=layers.init_attn,
    init_cache=layers.init_attn_cache,
    forward=layers.attn_forward,
    prefill=layers.attn_prefill,
    decode=layers.attn_decode,
    init_page_pool=init_attn_page_pool,
    decode_paged=attn_decode_paged,
    prefill_chunk_paged=attn_prefill_chunk_paged,
    decode_multi_paged=attn_decode_multi_paged,
    # (P, page, KVH * HD) codes + (P, page, KVH) scale metadata: KV heads
    # (the lane axis splits into whole heads: KVH divides by the TP degree)
    paged_partition_spec={"k": 2, "v": 2, "k_scale": 2, "v_scale": 2},
))

MLA = register_backend(AttentionBackend(
    name="mla",
    paged_leaf_keys=("latent",),
    mask_families=("prefix",),
    paged_mask_families=("prefix",),
    init=layers.init_mla,
    init_cache=lambda cfg, batch, max_len, window=None, dtype=jnp.bfloat16:
        layers.init_mla_cache(cfg, batch, max_len, dtype=dtype),
    forward=lambda p, x, cfg, *, window=None, positions=None:
        layers.mla_forward(p, x, cfg, positions=positions),
    prefill=lambda p, x, cfg, cache, *, window=None:
        layers.mla_prefill(p, x, cfg, cache),
    decode=lambda p, x, cfg, cache, cur_pos, *, window=None:
        layers.mla_decode(p, x, cfg, cache, cur_pos),
    init_page_pool=init_mla_page_pool,
    decode_paged=mla_decode_paged,
    prefill_chunk_paged=mla_prefill_chunk_paged,
    decode_multi_paged=mla_decode_multi_paged,
    # the latent stream is shared by every head: heads shard (w_uk/w_uv
    # columns), the per-token latents replicate across the TP ring
    paged_partition_spec={"latent": None},
))
