"""Plain float32 reference for latent attention with sparse experts
(DeepSeek-V2-Lite, served as one chip's share of its routed experts).

As published (arXiv:2405.04434 and the released config.json): token
embedding; per layer an RMSNorm, multi-head latent attention, a residual
add, an RMSNorm, a feed-forward block and a residual add; a final RMSNorm
and an untied head.  Attention is computed the long way, per head: the
query ``h Wq`` splits into a no-position part and a rotary part; the
keys and values come from the latent ``c = RMSNorm(h W_dkv[:, :r])``
(``k_nope = c W_uk``, ``v = c W_uv``) and one rotary key ``h W_dkv[:, r:]``
shared by every head; rotary embeddings are YaRN's (inv_freq blended by
a linear ramp between the correction dimensions, cos and sin times
mscale(mscale) / mscale(mscale_all_dim)); scores are scaled by
``1/sqrt(nope + rope)`` times mscale(mscale_all_dim) squared.  Layers
before ``first_k_dense_replace`` have a SwiGLU MLP; the others route each
token by a softmax over every routed expert and keep the greedy top
``num_experts_per_tok`` weights, renormalised only if ``norm_topk_prob``,
times ``routed_scaling_factor``; they add the routed experts' SwiGLUs,
weighted, and the shared experts' SwiGLU.

The cut (the configuration file's ``deployment``): only the routed
experts this chip holds, ``0 .. n_routed_experts - 1``, are computed; a
token's weight on an expert held elsewhere adds nothing.  The router
still scores all ``n_routed_experts_published``.  Each held expert runs
only over the rows routed to it, in blocks of ``EB`` rows.

Departures from the published model: the weights are the benchmark's
seeded random ones (``bench.lib.weights``), made again here from the
program's leaf names; the rotary pairs are split-half (dimension ``i``
with ``i + rope/2``), where DeepSeek's code first de-interleaves pairs
``(2i, 2i+1)``: with seeded weights that is the same model up to a fixed
permutation of the rotary columns; and the logits of the padding rows
the served tables carry past ``vocab_size`` are dropped.

It imports nothing of the program.  Every matrix product runs in float32
under ``jax.default_matmul_precision("highest")``; the weights are the
served bfloat16 values (the router's float32), widened.  ``quant="fp8"``
is the control: every weight matrix, the router's too, and every cached
latent and rotary key rounded through float8 e4m3 (per output column /
per token scales), the step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights as W
from bench.reference.dense_gqa import _embed, _fp8, _head, _rms, padded_vocab

QB = 512                       # queries per attention block
EB = 256                       # rows per block of a routed expert


@functools.lru_cache(maxsize=None)
def dims(items: tuple) -> dict:
    m = dict(items)
    y = dict(m["rope_scaling"])
    return {"L": m["num_hidden_layers"], "d": m["hidden_size"],
            "h": m["num_attention_heads"], "nope": m["qk_nope_head_dim"],
            "rope": m["qk_rope_head_dim"], "v": m["v_head_dim"],
            "r": m["kv_lora_rank"], "ff": m["intermediate_size"],
            "eff": m["moe_intermediate_size"],
            "E": m["n_routed_experts_published"],
            "held": m["n_routed_experts"], "k": m["num_experts_per_tok"],
            "shared": m["n_shared_experts"],
            "dense": m["first_k_dense_replace"], "norm": m["norm_topk_prob"],
            "scaling": m["routed_scaling_factor"], "theta": m["rope_theta"],
            "eps": m["rms_norm_eps"], "yarn": y}


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, y: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies for ``dim`` rotary dimensions."""
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    orig = y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    return base / y["factor"] * ramp + base * (1.0 - ramp)


def softmax_scale(m: dict) -> float:
    y = m["yarn"]
    s = 1.0 / math.sqrt(m["nope"] + m["rope"])
    if y.get("mscale_all_dim"):
        s *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return s


def _rope(x, pos, m):
    """x (T, ..., D) rotated split-half at positions ``pos`` (T,)."""
    d, y = x.shape[-1], m["yarn"]
    inv = jnp.asarray(yarn_inv_freq(d, m["theta"], y), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv
    gain = (_mscale(y["factor"], y.get("mscale", 1.0))
            / _mscale(y["factor"], y.get("mscale_all_dim", 0.0)))
    cos = (jnp.cos(ang) * gain).reshape((-1,) + (1,) * (x.ndim - 2)
                                        + (d // 2,))
    sin = (jnp.sin(ang) * gain).reshape(cos.shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _leaf(base, seg: int, layer: int, name: str, shape, quant, axis=0):
    dt = jnp.float32 if name.endswith(("ln1", "ln2", "_norm", "router")) \
        else jnp.bfloat16
    w = W.layer_leaf(base, f"stacks/{seg}/0/{name}", layer, shape,
                     dt).astype(jnp.float32)
    is_norm = name.endswith(("ln1", "ln2", "_norm"))
    return _fp8(w, axis) if quant and not is_norm else w


def _attention(q, k, v, seg, pos, span, scale):
    """Causal attention of packed sequences: q, k (T, H, Dk), v (T, H, Dv);
    a query sees keys of its own sequence at positions up to its own.
    Blocks of ``QB`` queries look back ``span`` rows."""
    t, h, _ = q.shape
    ks = span + QB

    def block(b):
        q0 = b * QB
        k0 = jnp.clip(q0 - span, 0, t - ks)
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QB)
        kb = jax.lax.dynamic_slice_in_dim(k, k0, ks)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, ks)
        sq = jax.lax.dynamic_slice_in_dim(seg, q0, QB)
        sk = jax.lax.dynamic_slice_in_dim(seg, k0, ks)
        pq = jax.lax.dynamic_slice_in_dim(pos, q0, QB)
        pk = jax.lax.dynamic_slice_in_dim(pos, k0, ks)
        ok = (sq[:, None] == sk[None, :]) & (pk[None, :] <= pq[:, None])
        s = jnp.einsum("qhd,khd->hqk", qb, kb) * scale
        s = jnp.where(ok[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vb)

    return jax.lax.map(block, jnp.arange(t // QB)).reshape(t, h, -1)


def _mla(x, seg_ids, pos, base, seg, layer, m, quant, span):
    t = x.shape[0]
    h, nope, rope, r = m["h"], m["nope"], m["rope"], m["r"]
    a = lambda name, shape: _leaf(base, seg, layer, f"attn/{name}", shape,
                                  quant)
    hn = _rms(x, _leaf(base, seg, layer, "ln1", (m["d"],), quant), m["eps"])
    q = (hn @ a("wq", (m["d"], h * (nope + rope)))).reshape(t, h, nope + rope)
    ckv = hn @ a("w_dkv", (m["d"], r + rope))
    c = _rms(ckv[:, :r], a("kv_norm", (r,)), m["eps"])
    k_pe = _rope(ckv[:, r:], pos, m)
    if quant:                          # the cached latent and rotary key
        c, k_pe = _fp8(c, -1), _fp8(k_pe, -1)
    k_nope = (c @ a("w_uk", (r, h * nope))).reshape(t, h, nope)
    v = (c @ a("w_uv", (r, h * m["v"]))).reshape(t, h, m["v"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, m)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None, :],
                                                  (t, h, rope))], -1)
    o = _attention(q, k, v, seg_ids, pos, span, softmax_scale(m))
    return x + o.reshape(t, -1) @ a("wo", (h * m["v"], m["d"]))


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _held_experts(hn, weight, wg, wu, wd):
    """Sum over the held experts of each one's SwiGLU of the rows routed to
    it, times the rows' weights ``weight`` (T, held; zero where a row did
    not pick the expert): only those rows are computed, ``EB`` at a
    time."""
    t = hn.shape[0]
    out = jnp.zeros_like(hn)
    for e in range(weight.shape[1]):
        routed = weight[:, e] > 0
        rows = jnp.argsort(~routed, stable=True)         # routed rows first
        rows = jnp.concatenate([rows, jnp.zeros(EB, rows.dtype)])
        n = jnp.sum(routed)

        def block(i, acc, e=e, rows=rows, n=n):
            at = jax.lax.dynamic_slice_in_dim(rows, i * EB, EB)
            ok = (i * EB + jnp.arange(EB)) < n
            y = _swiglu(hn[at], wg[e], wu[e], wd[e])
            w = jnp.where(ok, weight[at, e], 0.0)
            return acc.at[at].add(y * w[:, None])

        out = jax.lax.fori_loop(0, (n + EB - 1) // EB, block, out)
    return out


def _ffn(x, base, seg, layer, m, quant):
    d = m["d"]
    hn = _rms(x, _leaf(base, seg, layer, "ln2", (d,), quant), m["eps"])
    if seg == 0:                                        # dense layers
        f = lambda n, s: _leaf(base, seg, layer, f"mlp/{n}", s, quant)
        return x + _swiglu(hn, f("w_gate", (d, m["ff"])),
                           f("w_up", (d, m["ff"])),
                           f("w_down", (m["ff"], d)))
    f = lambda n, s: _leaf(base, seg, layer, f"moe/{n}", s, quant)
    e, eff, fs = m["held"], m["eff"], m["eff"] * m["shared"]
    probs = jax.nn.softmax(hn @ f("router", (d, m["E"])), axis=-1)
    w, ids = jax.lax.top_k(probs, m["k"])
    if m["norm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * m["scaling"]
    weight = jnp.sum(jnp.where(ids[:, :, None] == jnp.arange(e), w[..., None],
                               0.0), axis=1)            # (T, held)
    # each layer's experts are one (held, in, out) leaf: the fp8 control
    # scales each expert's output columns
    g = lambda n, s: _leaf(base, seg, layer, f"moe/{n}", s, quant, axis=1)
    wg, wu = g("w_gate", (e, d, eff)), g("w_up", (e, d, eff))
    wd = g("w_down", (e, eff, d))
    y = _held_experts(hn, weight, wg, wu, wd)
    y = y + _swiglu(hn, f("shared/w_gate", (d, fs)),
                    f("shared/w_up", (d, fs)), f("shared/w_down", (fs, d)))
    return x + y


@functools.partial(jax.jit,
                   static_argnames=("items", "seg", "quant", "span"))
def _layer(x, seg_ids, pos, base, layer, *, items, seg, quant, span):
    m = dims(items)
    x = _mla(x, seg_ids, pos, base, seg, layer, m, quant, span)
    return _ffn(x, base, seg, layer, m, quant)


def _hashable(v):
    return tuple(sorted(v.items())) if isinstance(v, dict) else v


def logits(model: dict, serving: dict, seed: int, seqs, at, *,
           quant: str | None = None):
    """Float32 logits (N, vocab_size) at positions ``at[i]`` of sequence
    ``seqs[i]`` (one row per position, in order), on the default device.

    ``seqs``: int32 token arrays; ``at``: int arrays of positions whose
    next-token logits are wanted."""
    items = tuple(sorted((k, _hashable(v)) for k, v in model.items()))
    m = dims(items)
    vpad = padded_vocab(model, serving)
    if max(len(s) for s in seqs) > serving["max_len"]:
        raise ValueError("a sequence is longer than the served max_len")
    if quant not in (None, "fp8"):
        raise ValueError(f"quant={quant!r} (want None or 'fp8')")
    span = -(-serving["max_len"] // QB) * QB
    n = sum(len(s) for s in seqs)
    t = max(-(-n // 4096) * 4096, span + QB)
    tokens = np.zeros(t, np.int32)
    seg_ids = np.full(t, -1, np.int32)
    pos = np.zeros(t, np.int32)
    rows, o = [], 0
    for i, (s, a) in enumerate(zip(seqs, at)):
        tokens[o:o + len(s)] = s
        seg_ids[o:o + len(s)] = i
        pos[o:o + len(s)] = np.arange(len(s))
        rows.append(o + np.asarray(a, np.int64))
        o += len(s)
    rows = jnp.asarray(np.concatenate(rows).astype(np.int32))
    q = bool(quant)
    with jax.default_matmul_precision("highest"):
        seg_d, pos_d = jnp.asarray(seg_ids), jnp.asarray(pos)
        base = W.base_key(seed)
        x = _embed(jnp.asarray(tokens), base, items=items, vpad=vpad, quant=q)
        for layer in range(m["L"]):
            seg, li = (0, layer) if layer < m["dense"] else (
                1, layer - m["dense"])
            x = _layer(x, seg_d, pos_d, base, jnp.int32(li), items=items,
                       seg=seg, quant=q, span=span)
        return _head(x[rows], base, items=items, vpad=vpad, quant=q)
