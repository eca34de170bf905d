"""Shared model substrate: config, initializers, norms, RoPE, attention.

Everything is pure-functional JAX over explicit parameter pytrees.  Layer
stacks are stored with a leading layer axis and executed with
``jax.lax.scan`` so the lowered HLO is O(1) in depth (essential for the
CPU dry-run of 40-48 layer configs, and the production-correct choice).

Attention is implemented **blocked** (flash-style online softmax over KV
blocks in pure ``lax``), so prefill at 32k context never materializes an
(S x S) score matrix — the JAX analogue of the paper's KV$-streaming SDPA
phase.  The Pallas decode kernel in ``kernels/decode_attention`` is the
TPU-optimized version of the decode path; the functions here are the
reference implementations used for training, prefill, and CPU tests.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"          # dense | moe | ssm | hybrid | encoder | vlm
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 0              # 0 -> d_model // n_heads
    d_ff: int = 512
    vocab_size: int = 512

    # attention options
    qk_norm: bool = False
    qkv_bias: bool = False
    sliding_window: int | None = None   # SWA window (tokens)
    global_attn_every: int = 0          # hybrid SWA/global interleave (0=never)
    rope_theta: float = 10000.0
    rope_scaling: "Yarn | None" = None  # YaRN; None = plain RoPE
    causal: bool = True                 # False => encoder-only

    # MLA (DeepSeek)
    mla: bool = False
    kv_lora_rank: int = 512
    rope_head_dim: int = 64
    v_head_dim: int = 0                 # 0 -> head_dim

    # MoE
    moe: bool = False
    n_experts: int = 0
    n_experts_per_token: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    first_dense_layers: int = 0
    moe_layer_period: int = 1           # 1 = every layer is MoE
    norm_topk_prob: bool = True         # renormalise the top-k weights
    # routed experts this device holds, 0 .. n_experts_held - 1 (its share
    # of an expert-parallel deployment; the router keeps all n_experts
    # outputs).  0 = all of them.
    n_experts_held: int = 0

    # SSM (Mamba2 SSD)
    ssm: bool = False
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_kernel: int = 4
    hybrid: bool = False                # Hymba: parallel attn + ssm heads

    # modality frontends (stubs; embeddings come via input_specs)
    frontend: str | None = None         # "audio" | "vision"
    n_frontend_tokens: int = 0          # e.g. image tokens prepended

    tie_embeddings: bool = False
    norm_eps: float = 1e-5

    # Vocab padding (Megatron-style): embedding/head tables are padded to a
    # multiple so they shard evenly over any TP degree in the mesh zoo
    # (16-way model TP and the 512-way multi-pod ring).  Padded logit
    # columns are masked to -inf in the head.  1 = no padding (smoke tests).
    vocab_pad_multiple: int = 1

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return -(-self.vocab_size // m) * m

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def v_hd(self) -> int:
        return self.v_head_dim or self.hd

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def mla_softmax_scale(self) -> float:
        """MLA's score scale: 1/sqrt(q head dim), times YaRN's mscale
        squared where the scaling names ``mscale_all_dim``."""
        scale = 1.0 / math.sqrt(self.hd + self.rope_head_dim)
        y = self.rope_scaling
        if y is not None and y.mscale_all_dim:
            scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
        return scale

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.ssm_heads or (self.d_inner // self.ssm_head_dim)

    @property
    def attn_impl_window(self) -> int | None:
        return self.sliding_window

    def is_moe_layer(self, layer_idx: int) -> bool:
        if not self.moe:
            return False
        if layer_idx < self.first_dense_layers:
            return False
        return (layer_idx - self.first_dense_layers) % self.moe_layer_period == 0

    @property
    def has_decode(self) -> bool:
        return self.causal

    @property
    def subquadratic(self) -> bool:
        """Can this arch run the 500k-token long-context decode shape?"""
        return self.family in ("ssm", "hybrid") or self.sliding_window is not None


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling, as DeepSeek-V2's ``rope_scaling`` of type
    ``yarn`` states it (arXiv:2309.00071)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(y: Yarn, dim: int, theta: float) -> tuple[int, int]:
    """The rotary pairs between which YaRN blends the interpolated and the
    original frequencies (DeepSeek-V2's ``yarn_find_correction_range``)."""
    def at(rotations):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(at(y.beta_fast)), 0),
            min(math.ceil(at(y.beta_slow)), dim - 1))


PARAM_DTYPE = jnp.bfloat16
NORM_DTYPE = jnp.float32


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype=PARAM_DTYPE) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=PARAM_DTYPE) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Norms & activations (HP-VOPs analogue: fp32 internals)
# ---------------------------------------------------------------------------


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return out.astype(x.dtype)


def gated_rmsnorm(x: jnp.ndarray, z: jnp.ndarray, w: jnp.ndarray,
                  eps: float = 1e-5) -> jnp.ndarray:
    """Mamba2 norm: RMSNorm(x * silu(z))."""
    xf = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def swiglu(x: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    from repro.parallel.hints import tp_row_dot
    from repro.quant.linear import qdot
    g = qdot(x, w_gate)
    u = qdot(x, w_up)
    return tp_row_dot(jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u,
                      w_down)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float,
               scaling: Yarn | None = None) -> jnp.ndarray:
    """Rotary frequencies; under YaRN the pairs past the correction range
    take the interpolated ones (``1 / factor``), those before it the
    original, and a linear ramp blends the pairs in between."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if scaling is not None:
        low, high = yarn_correction_range(scaling, head_dim, theta)
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        freqs = freqs / scaling.factor * ramp + freqs * (1.0 - ramp)
    return freqs


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling: Yarn | None = None) -> jnp.ndarray:
    """x: (B, S, H, D) split-half convention; positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)              # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(ang)[..., None, :]                   # (B, S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked (flash-style) attention in pure lax — the KV$-streaming SDPA
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _gqa_expand(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(B, S, KVH, D) -> (B, S, H, D) by repeating groups."""
    b, s, kvh, d = k.shape
    rep = n_heads // kvh
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def blocked_attention(
    q: jnp.ndarray,              # (B, Sq, H, D)
    k: jnp.ndarray,              # (B, Skv, KVH, D)
    v: jnp.ndarray,              # (B, Skv, KVH, Dv)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int | jnp.ndarray = 0,
    q_block: int = 512,
    kv_block: int = 512,
    scale: float | None = None,
) -> jnp.ndarray:
    """Online-softmax attention over KV blocks; never builds (Sq x Skv).

    ``q_offset`` is the absolute position of q[:, 0] (for prefill
    continuation / decode): a scalar, or a ``(B,)`` array for ragged
    continuation (chunked paged prefill, where every slot resumes at its
    own position).  fp32 softmax state (HP-VOPs analogue).
    """
    b, sq, h, d = q.shape
    per_row = getattr(q_offset, "ndim", 0) == 1
    _, skv, kvh, dv = v.shape[0], v.shape[1], v.shape[2], v.shape[3]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    qb = min(q_block, sq)
    kb = min(kv_block, skv)
    # pad sequences up to block multiples
    sq_p = -(-sq // qb) * qb
    skv_p = -(-skv // kb) * kb
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))

    k = _gqa_expand(k, h)
    v = _gqa_expand(v, h)

    nq, nk = sq_p // qb, skv_p // kb
    qr = q.reshape(b, nq, qb, h, d).astype(jnp.float32)
    kr = k.reshape(b, nk, kb, h, d).astype(jnp.float32)
    vr = v.reshape(b, nk, kb, h, dv).astype(jnp.float32)

    if per_row:
        # (B, nq, qb) absolute positions, one offset per batch row
        q_pos = (jnp.asarray(q_offset, jnp.int32)[:, None, None]
                 + jnp.arange(sq_p).reshape(nq, qb)[None])
    else:
        q_pos = q_offset + jnp.arange(sq_p).reshape(nq, qb)
    k_pos = jnp.arange(skv_p).reshape(nk, kb)
    kv_valid = (jnp.arange(skv_p) < skv).reshape(nk, kb)

    def q_block_fn(qi, q_blk):
        # q_blk: (B, qb, H, D); scan over kv blocks
        qp = q_pos[:, qi] if per_row else q_pos[qi]        # (B, qb) | (qb,)

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, k_blk, v_blk = inp
            kp = k_pos[kj]                                 # (kb,)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk) * scale
            if per_row:
                mask = kv_valid[kj][None, None, :]         # (1, 1, kb)
                if causal:
                    mask = mask & (kp[None, None, :] <= qp[:, :, None])
                if window is not None:
                    mask = mask & (qp[:, :, None] - kp[None, None, :] < window)
                s = jnp.where(mask[:, None], s, NEG_INF)   # (B, 1, qb, kb)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = (acc * corr[..., None]
                           + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk))
                return (m_new, l_new, acc_new), None
            mask = kv_valid[kj][None, :]                   # (1, kb)
            if causal:
                mask = mask & (kp[None, :] <= qp[:, None])
            if window is not None:
                mask = mask & (qp[:, None] - kp[None, :] < window)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, qb), jnp.float32)
        a0 = jnp.zeros((b, h, qb, dv), jnp.float32)
        ks = jnp.arange(nk)
        # checkpoint the kv step: the backward pass recomputes each block's
        # probability matrix instead of saving all nk of them — the
        # flash-attention memory contract ((B,H,qb,kb) x nk would dominate
        # training HBM at 4k x 256 x 40L).
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (m0, l0, a0),
            (ks, jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.transpose(out, (0, 2, 1, 3))            # (B, qb, H, Dv)

    # checkpoint per q-block as well: the backward otherwise stacks every
    # block's (m, l, acc) kv-scan carries (nq x nk x (B,H,qb,dv) f32).
    outs = jax.lax.map(jax.checkpoint(
        lambda args: q_block_fn(args[0], args[1])),
        (jnp.arange(nq), jnp.moveaxis(qr, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq_p, h, dv)[:, :sq]
    return out.astype(q.dtype)


def cache_update_at(cache_arr: jnp.ndarray, new: jnp.ndarray, slot) -> jnp.ndarray:
    """Write one token's entry at dynamic position ``slot`` along axis 1.

    Uses an elementwise select instead of ``dynamic_update_slice``: DUS at
    a dynamic index on a context-sharded (S-partitioned) cache forces
    GSPMD into involuntary full rematerialization — the cache is
    all-gathered, updated, and re-sharded EVERY layer, turning a one-token
    write into a full cache read+write (measured 24x memory-term blowup on
    decode cells; EXPERIMENTS.md §Perf iteration 1).  The select is
    elementwise, so every shard updates locally.

    ``new``: (B, 1, ...) broadcastable against ``cache_arr`` (B, S, ...).
    """
    s = cache_arr.shape[1]
    iota_shape = (1, s) + (1,) * (cache_arr.ndim - 2)
    iota = jax.lax.broadcasted_iota(jnp.int32, iota_shape, 1)
    return jnp.where(iota == slot, new.astype(cache_arr.dtype), cache_arr)


def decode_attention_ref(
    q: jnp.ndarray,              # (B, H, D) — one new token per sequence
    k_cache: jnp.ndarray,        # (B, S, KVH, D)
    v_cache: jnp.ndarray,        # (B, S, KVH, Dv)
    cur_len: jnp.ndarray | None = None,   # (B,) int32 — #valid positions
    *,
    valid: jnp.ndarray | None = None,     # (S,) or (B, S) bool mask
    scale: float | None = None,
) -> jnp.ndarray:
    """Single-token decode attention (pure-jnp oracle for the Pallas kernel).

    Pass either ``cur_len`` (prefix-valid cache) or an explicit ``valid``
    mask (ring-buffer sliding-window caches).
    """
    b, h, d = q.shape
    kvh = k_cache.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    rep = h // kvh
    qf = q.astype(jnp.float32).reshape(b, kvh, rep, d)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bgrd,bsgd->bgrs", qf, kf) * scale
    if valid is None:
        valid = jnp.arange(k_cache.shape[1])[None, :] < cur_len[:, None]
    if valid.ndim == 1:
        valid = valid[None, :]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", p, vf)
    return out.reshape(b, h, vf.shape[-1]).astype(q.dtype)


def count_params(params) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params)))
