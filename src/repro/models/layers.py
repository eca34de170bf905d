"""Attention blocks: GQA (with SWA / qk-norm / bias variants) and MLA.

Each block kind exposes ``init_<kind>`` and three apply paths:
  * ``forward``  — full-sequence (training / prefill without cache)
  * ``prefill``  — full-sequence while materializing the decode cache
  * ``decode``   — single-token step against the cache

Caches are plain dicts of arrays so they stack cleanly along a layer axis
for ``lax.scan`` (see ``runtime.kv_cache`` for the container types).

The paged (continuous-batching) apply paths live in
``models/attention_backends.py``, which registers each family here behind
the attention-backend registry the model assembly dispatches through.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import (
    ModelConfig, NEG_INF, apply_rope, blocked_attention, decode_attention_ref,
    dense_init, rmsnorm, split_keys, swiglu,
)
from repro.parallel.hints import shard_hint
from repro.quant.linear import qdot

# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def init_attn(key, cfg: ModelConfig) -> dict:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd),
        "wk": dense_init(ks[1], d, kvh * hd),
        "wv": dense_init(ks[2], d, kvh * hd),
        "wo": dense_init(ks[3], h * hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), jnp.bfloat16)
        p["bk"] = jnp.zeros((kvh * hd,), jnp.bfloat16)
        p["bv"] = jnp.zeros((kvh * hd,), jnp.bfloat16)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _qkv(p: dict, x: jnp.ndarray, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = qdot(x, p["wq"])
    k = qdot(x, p["wk"])
    v = qdot(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    q = shard_hint(q, "act_bshd")
    k = shard_hint(k, "act_bskd")
    return q, k, v


def attn_forward(p: dict, x: jnp.ndarray, cfg: ModelConfig, *,
                 window=None, positions=None) -> jnp.ndarray:
    """Full-sequence attention.  ``window``: None | int | traced scalar."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    out = blocked_attention(q, k, v, causal=cfg.causal, window=window)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd)
    return qdot(out, p["wo"])


def attn_prefill(p: dict, x: jnp.ndarray, cfg: ModelConfig, cache: dict, *,
                 window=None) -> tuple[jnp.ndarray, dict]:
    """Prefill: run attention and write k/v into the cache at [0, s)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    out = blocked_attention(q, k, v, causal=cfg.causal, window=window)
    out = qdot(out.reshape(b, s, cfg.n_heads * cfg.hd), p["wo"])
    w = cache["k"].shape[1]
    if w >= s:
        new_k = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                             (0, 0, 0, 0))
        new_v = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                             (0, 0, 0, 0))
        pos = jnp.arange(w)
        slot_pos = jnp.where(pos < s, pos, -1)
    else:  # sliding-window cache smaller than prefill: keep the tail
        new_k = k[:, s - w:].astype(cache["k"].dtype)
        new_v = v[:, s - w:].astype(cache["v"].dtype)
        # ring layout: slot j holds absolute position t ≡ j (mod w)
        tail = jnp.arange(s - w, s)
        slot = tail % w
        slot_pos = jnp.zeros((w,), jnp.int32).at[slot].set(tail)
        new_k = jnp.zeros_like(cache["k"]).at[:, slot].set(new_k)
        new_v = jnp.zeros_like(cache["v"]).at[:, slot].set(new_v)
    return out, {"k": new_k, "v": new_v, "slot_pos": slot_pos.astype(jnp.int32)}


def attn_decode(p: dict, x: jnp.ndarray, cfg: ModelConfig, cache: dict,
                cur_pos, *, window=None) -> tuple[jnp.ndarray, dict]:
    """One-token step.  x: (B, D); cur_pos: scalar int32 (position index)."""
    b, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    positions = jnp.full((b, 1), cur_pos)
    q, k, v = _qkv(p, x[:, None, :], cfg, positions)
    w = cache["k"].shape[1]
    slot = jnp.mod(cur_pos, w)
    new_k = common.cache_update_at(cache["k"], k, slot)
    new_v = common.cache_update_at(cache["v"], v, slot)
    slot_pos = cache["slot_pos"].at[slot].set(cur_pos)

    valid = (slot_pos >= 0) & (slot_pos <= cur_pos)
    if window is not None:
        valid = valid & (slot_pos > cur_pos - window)
    out = decode_attention_ref(
        q[:, 0], new_k, new_v, None, valid=valid[None, :].repeat(b, 0))
    out = qdot(out.reshape(b, h * hd), p["wo"])
    return out, {"k": new_k, "v": new_v, "slot_pos": slot_pos}


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int,
                    window: int | None = None,
                    dtype=jnp.bfloat16) -> dict:
    w = min(max_len, window) if window else max_len
    return {
        "k": jnp.zeros((batch, w, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, w, cfg.n_kv_heads, cfg.hd), dtype),
        "slot_pos": jnp.full((w,), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    hd, rhd, vhd, r = cfg.hd, cfg.rope_head_dim, cfg.v_hd, cfg.kv_lora_rank
    ks = split_keys(key, 6)
    return {
        "wq": dense_init(ks[0], d, h * (hd + rhd)),
        "w_dkv": dense_init(ks[1], d, r + rhd),      # latent + shared k_rope
        "kv_norm": jnp.ones((r,), jnp.float32),
        "w_uk": dense_init(ks[2], r, h * hd),
        "w_uv": dense_init(ks[3], r, h * vhd),
        "wo": dense_init(ks[4], h * vhd, d),
    }


def _mla_qc(p, x, cfg: ModelConfig, positions):
    """Shared q / latent computation.  Returns q_nope, q_rope, c_kv, k_rope."""
    b, s, _ = x.shape
    h, hd, rhd, r = cfg.n_heads, cfg.hd, cfg.rope_head_dim, cfg.kv_lora_rank
    q = qdot(x, p["wq"]).reshape(b, s, h, hd + rhd)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    ckr = x @ p["w_dkv"]
    c_kv = rmsnorm(ckr[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(ckr[..., None, r:], positions, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(p: dict, x: jnp.ndarray, cfg: ModelConfig, *,
                positions=None) -> jnp.ndarray:
    b, s, _ = x.shape
    h, hd, rhd, vhd = cfg.n_heads, cfg.hd, cfg.rope_head_dim, cfg.v_hd
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q_nope, q_rope, c_kv, k_rope = _mla_qc(p, x, cfg, positions)
    # expand per-head keys/values from the latent (prefill path)
    k_nope = (c_kv @ p["w_uk"]).reshape(b, s, h, hd)
    v = (c_kv @ p["w_uv"]).reshape(b, s, h, vhd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                  (b, s, h, rhd))], axis=-1)
    scale = cfg.mla_softmax_scale
    out = blocked_attention(q, k, v, causal=cfg.causal, scale=scale)
    return qdot(out.reshape(b, s, h * vhd), p["wo"])


def mla_prefill(p, x, cfg: ModelConfig, cache: dict):
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    out = mla_forward(p, x, cfg, positions=positions)
    _, _, c_kv, k_rope = _mla_qc(p, x, cfg, positions)
    new_c = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0))
    new_kr = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, 0, 0))
    w = cache["c_kv"].shape[1]
    pos = jnp.arange(w)
    slot_pos = jnp.where(pos < s, pos, -1).astype(jnp.int32)
    return out, {"c_kv": new_c, "k_rope": new_kr, "slot_pos": slot_pos}


def mla_decode(p, x, cfg: ModelConfig, cache: dict, cur_pos):
    """Absorbed-matmul MLA decode: attention in the latent space.

    score_h(t) = q_nope_h · (W_uk^T)_h c_t + q_rope_h · k_rope_t
    out_h      = (Σ_t p_t c_t) @ (W_uv)_h
    """
    b, d = x.shape
    h, hd, rhd, vhd, r = cfg.n_heads, cfg.hd, cfg.rope_head_dim, cfg.v_hd, cfg.kv_lora_rank
    positions = jnp.full((b, 1), cur_pos)
    q_nope, q_rope, c_kv, k_rope = _mla_qc(p, x[:, None, :], cfg, positions)
    slot = cur_pos  # full cache (no SWA for MLA archs)
    new_c = common.cache_update_at(cache["c_kv"], c_kv, slot)
    new_kr = common.cache_update_at(cache["k_rope"], k_rope, slot)
    slot_pos = cache["slot_pos"].at[slot].set(cur_pos)

    # absorb W_uk into q: (B, H, r)
    w_uk = p["w_uk"].reshape(r, h, hd)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    q_eff = jnp.concatenate([q_lat, q_rope[:, 0].astype(jnp.float32)], axis=-1)
    k_eff = jnp.concatenate([new_c.astype(jnp.float32),
                             new_kr.astype(jnp.float32)], axis=-1)  # (B,S,r+rhd)
    scale = cfg.mla_softmax_scale
    s_ = jnp.einsum("bhr,bsr->bhs", q_eff, k_eff) * scale
    valid = (slot_pos >= 0) & (slot_pos <= cur_pos)
    s_ = jnp.where(valid[None, None, :], s_, NEG_INF)
    pattn = jax.nn.softmax(s_, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", pattn, new_c.astype(jnp.float32))  # latent ctx
    w_uv = p["w_uv"].reshape(r, h, vhd)
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv.astype(jnp.float32))
    out = qdot(out.reshape(b, h * vhd).astype(x.dtype), p["wo"])
    return out, {"c_kv": new_c, "k_rope": new_kr, "slot_pos": slot_pos}


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.rope_head_dim), dtype),
        "slot_pos": jnp.full((max_len,), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = split_keys(key, 3)
    return {
        "w_gate": dense_init(ks[0], d, f),
        "w_up": dense_init(ks[1], d, f),
        "w_down": dense_init(ks[2], f, d),
    }


def mlp_forward(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
