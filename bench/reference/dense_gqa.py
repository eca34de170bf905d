"""Plain float32 reference for the dense decoder family (phi3, danube).

A Llama-style decoder as published: token embedding; per layer an RMSNorm,
grouped-query attention with split-half rotary embeddings and an optional
sliding window (a query at position p sees keys at p - window < k <= p),
a residual add, an RMSNorm, a SwiGLU MLP and a residual add; a final
RMSNorm and an untied output head.  Departures from the published models:
the weights are the benchmark's seeded random ones (``bench.lib.weights``),
made again here from the seed, and the logits of the padding rows the
served tables carry past ``vocab_size`` are dropped.

It imports nothing of the program.  Every matrix product runs in float32
under ``jax.default_matmul_precision("highest")``; the weights are the
served bfloat16 values, widened.  The sequences to score are packed into
one buffer and run layer by layer, with attention in blocks of queries
over the keys that can reach them, so the whole pass fits on one chip
next to nothing else.

``quant="fp8"`` is the control: every weight matrix and every key and
value rounded through float8 e4m3 (per output column / per token and head
scales), the step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights as W

QB = 512                       # queries per attention block
LEAVES = {"ln1": "stacks/0/0/ln1", "ln2": "stacks/0/0/ln2",
          "wq": "stacks/0/0/attn/wq", "wk": "stacks/0/0/attn/wk",
          "wv": "stacks/0/0/attn/wv", "wo": "stacks/0/0/attn/wo",
          "w_gate": "stacks/0/0/mlp/w_gate", "w_up": "stacks/0/0/mlp/w_up",
          "w_down": "stacks/0/0/mlp/w_down"}


@functools.lru_cache(maxsize=None)
def dims(items: tuple) -> dict:
    m = dict(items)
    h, kvh, d = m["num_attention_heads"], m["num_key_value_heads"], \
        m["hidden_size"]
    hd = m.get("head_dim") or d // h
    return {"L": m["num_hidden_layers"], "d": d, "h": h, "kvh": kvh,
            "hd": hd, "ff": m["intermediate_size"], "V": m["vocab_size"],
            "window": m.get("sliding_window"), "theta": m["rope_theta"],
            "eps": m["rms_norm_eps"]}


def padded_vocab(model: dict, serving: dict) -> int:
    m = serving["vocab_pad_multiple"]
    return -(-model["vocab_size"] // m) * m


def _fp8(x, axis):
    """Round through float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (T, H, D), split-half rotary embedding at positions ``pos`` (T,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer_weights(base, layer, m, quant):
    shapes = {"ln1": (m["d"],), "ln2": (m["d"],),
              "wq": (m["d"], m["h"] * m["hd"]),
              "wk": (m["d"], m["kvh"] * m["hd"]),
              "wv": (m["d"], m["kvh"] * m["hd"]),
              "wo": (m["h"] * m["hd"], m["d"]),
              "w_gate": (m["d"], m["ff"]), "w_up": (m["d"], m["ff"]),
              "w_down": (m["ff"], m["d"])}
    out = {}
    for k, shp in shapes.items():
        dt = jnp.float32 if k.startswith("ln") else jnp.bfloat16
        w = W.layer_leaf(base, LEAVES[k], layer, shp, dt).astype(jnp.float32)
        out[k] = _fp8(w, 0) if quant and not k.startswith("ln") else w
    return out


def _attention(q, k, v, seg, pos, window, span):
    """Causal attention of packed sequences: q (T, H, D), k/v (T, KVH, D);
    a query sees keys of its own sequence (``seg``) at positions in
    ``(pos - window, pos]``.  Blocks of ``QB`` queries look back ``span``
    rows, which covers the longest context a query can have."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    ks = span + QB
    scale = 1.0 / math.sqrt(d)

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
        if window is not None:
            ok &= pq[:, None] - pk[None, :] < window
        s = jnp.einsum("qhd,khd->hqk", qb, kb) * scale
        s = jnp.where(ok[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vb)

    out = jax.lax.map(block, jnp.arange(t // QB))
    return out.reshape(t, h, d)


@functools.partial(jax.jit, static_argnames=("items", "quant", "span"))
def _layer(x, seg, pos, base, layer, *, items, quant, span):
    m = dims(items)
    w = _layer_weights(base, layer, m, quant)
    t = x.shape[0]
    hn = _rms(x, w["ln1"], m["eps"])
    q = (hn @ w["wq"]).reshape(t, m["h"], m["hd"])
    k = (hn @ w["wk"]).reshape(t, m["kvh"], m["hd"])
    v = (hn @ w["wv"]).reshape(t, m["kvh"], m["hd"])
    q = _rope(q, pos, m["theta"])
    k = _rope(k, pos, m["theta"])
    if quant:
        k, v = _fp8(k, -1), _fp8(v, -1)
    a = _attention(q, k, v, seg, pos, m["window"], span)
    x = x + a.reshape(t, -1) @ w["wo"]
    hn = _rms(x, w["ln2"], m["eps"])
    x = x + (jax.nn.silu(hn @ w["w_gate"]) * (hn @ w["w_up"])) @ w["w_down"]
    return x


@functools.partial(jax.jit, static_argnames=("items", "vpad", "quant"))
def _embed(tokens, base, *, items, vpad, quant):
    m = dims(items)
    e = W.leaf_values(W.leaf_key(base, "embed"), (vpad, m["d"]), "embed",
                      jnp.bfloat16).astype(jnp.float32)
    if quant:
        e = _fp8(e, 1)
    return e[tokens]


@functools.partial(jax.jit, static_argnames=("items", "vpad", "quant"))
def _head(x, base, *, items, vpad, quant):
    m = dims(items)
    norm = W.leaf_values(W.leaf_key(base, "final_norm"), (m["d"],),
                         "final_norm", jnp.float32)
    head = W.leaf_values(W.leaf_key(base, "head"), (m["d"], vpad), "head",
                         jnp.bfloat16).astype(jnp.float32)
    if quant:
        head = _fp8(head, 0)
    return _rms(x, norm, m["eps"]) @ head[:, :m["V"]]


def logits(model: dict, serving: dict, seed: int, seqs, at, *,
           quant: str | None = None):
    """Float32 logits (N, vocab_size) at positions ``at[i]`` of sequence
    ``seqs[i]`` (one row per position, in order), on the default device.

    ``seqs``: int32 token arrays; ``at``: int arrays of positions whose
    next-token logits are wanted."""
    items = tuple(sorted(model.items()))
    m = dims(items)
    vpad = padded_vocab(model, serving)
    if max(len(s) for s in seqs) > serving["max_len"]:
        raise ValueError("a sequence is longer than the served max_len")
    # the longest context a query can have; fixed per configuration, so
    # runs differ only in the packed length (a multiple of 4096)
    span = min(serving["max_len"], m["window"] or serving["max_len"])
    span = -(-span // QB) * QB
    n = sum(len(s) for s in seqs)
    t = max(-(-n // 4096) * 4096, span + QB)
    tokens = np.zeros(t, np.int32)
    seg = np.full(t, -1, np.int32)
    pos = np.zeros(t, np.int32)
    rows, o = [], 0
    for i, (s, a) in enumerate(zip(seqs, at)):
        tokens[o:o + len(s)] = s
        seg[o:o + len(s)] = i
        pos[o:o + len(s)] = np.arange(len(s))
        rows.append(o + np.asarray(a, np.int64))
        o += len(s)
    rows = jnp.asarray(np.concatenate(rows).astype(np.int32))
    q = bool(quant)
    if quant not in (None, "fp8"):
        raise ValueError(f"quant={quant!r} (want None or 'fp8')")
    with jax.default_matmul_precision("highest"):
        seg_d, pos_d = jnp.asarray(seg), jnp.asarray(pos)
        base = W.base_key(seed)
        x = _embed(jnp.asarray(tokens), base, items=items, vpad=vpad, quant=q)
        for layer in range(m["L"]):
            x = _layer(x, seg_d, pos_d, base, jnp.int32(layer), items=items,
                       quant=q, span=span)
        return _head(x[rows], base, items=items, vpad=vpad, quant=q)
