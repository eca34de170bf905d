"""Mixture-of-Experts layer (Llama4-Maverick, DeepSeek-V2 style).

A config that holds only a share of the routed experts
(``n_experts_held``: one device's cut of an expert-parallel deployment)
runs ``moe_share``: routing over every expert, and the held experts'
rows computed without a capacity, so no row is ever dropped.  Otherwise
two execution strategies, selected by token count:

  * ``dense`` — every expert processes every token, combined with routing
    weights.  O(E x T) compute: only sane for tiny smoke configs, but it is
    the bit-exact reference for the property tests.
  * ``capacity`` — production path: tokens are sorted by expert id and
    gathered into an (E, C, D) buffer (capacity C with drop/pad semantics),
    processed with a single batched einsum whose expert axis shards over the
    mesh's ``model`` axis (expert parallelism), and scattered back.

The paper's Fig 10/11 treat MoE layers as the canonical memory-bound,
query-unique streaming phase; the capacity path preserves that structure
(each expert's weights are streamed once per step regardless of batch).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models import common
from repro.models.common import ModelConfig, dense_init, split_keys


def init_moe(key, cfg: ModelConfig) -> dict:
    ks = split_keys(key, 5)
    e, d, f = cfg.experts_held, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, jnp.float32),
        "w_gate": jax.vmap(lambda k: dense_init(k, d, f))(
            jax.random.split(ks[1], e)),
        "w_up": jax.vmap(lambda k: dense_init(k, d, f))(
            jax.random.split(ks[2], e)),
        "w_down": jax.vmap(lambda k: dense_init(k, f, d))(
            jax.random.split(ks[3], e)),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        ks2 = split_keys(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(ks2[0], d, fs),
            "w_up": dense_init(ks2[1], d, fs),
            "w_down": dense_init(ks2[2], fs, d),
        }
    return p


def _routing(x2d: jnp.ndarray, router: jnp.ndarray, k: int,
             normalize: bool = True):
    """Top-k softmax routing, the k weights renormalised to sum to one
    when ``normalize``.  Returns (weights (T,k) f32, ids (T,k) i32)."""
    logits = (x2d.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    if normalize:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, ids


class _Hits(threading.local):
    stack: list = []


_hits = _Hits()


@contextlib.contextmanager
def expert_hits():
    """``with expert_hits() as hits:`` -- every ``moe_share`` traced inside
    appends its ``(T, held experts)`` bool matrix of the rows routed to
    each held expert (the share's own counters, read by the caller)."""
    got: list = []
    _hits.stack = _hits.stack + [got]
    try:
        yield got
    finally:
        _hits.stack = _hits.stack[:-1]


def moe_share(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    """The held share's routed experts (0 .. held - 1), dropless: the
    router picks top-k over all ``n_experts``, and each held expert runs
    over every row with a combine weight of zero where the row did not
    pick it (so its weights stream once per call, whatever the row
    count).  Rows routed to experts held elsewhere add nothing here.
    Shared experts are not included."""
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    w, ids = _routing(x2d, p["router"], cfg.n_experts_per_token,
                      cfg.norm_topk_prob)
    held = jnp.arange(p["w_gate"].shape[0])
    hit = ids[:, :, None] == held                                 # (T,k,E)
    if _hits.stack:
        _hits.stack[-1].append(jnp.any(hit, axis=1))
    comb = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)    # (T, E)
    g = jnp.einsum("td,edf->etf", x2d, p["w_gate"])
    u = jnp.einsum("td,edf->etf", x2d, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y_all = jnp.einsum("etf,efd->etd", h, p["w_down"],
                       preferred_element_type=jnp.float32)
    y = jnp.einsum("te,etd->td", comb, y_all)
    return y.astype(x.dtype).reshape(b, s, d)


def _expert_ffn(xe: jnp.ndarray, p: dict) -> jnp.ndarray:
    """(E, C, D) -> (E, C, D) batched SwiGLU over the expert axis."""
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def moe_dense(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    """Reference: all experts on all tokens (tiny configs only)."""
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    w, ids = _routing(x2d, p["router"], cfg.n_experts_per_token,
                      cfg.norm_topk_prob)
    g = jnp.einsum("td,edf->tef", x2d, p["w_gate"])
    u = jnp.einsum("td,edf->tef", x2d, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y_all = jnp.einsum("tef,efd->ted", h, p["w_down"])           # (T, E, D)
    onehot = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32)  # (T,k,E)
    comb = jnp.einsum("tk,tke->te", w, onehot)                   # (T, E)
    y = jnp.einsum("te,ted->td", comb.astype(x.dtype), y_all)
    return y.reshape(b, s, d)


def _capacity_core(x2d: jnp.ndarray, w: jnp.ndarray, ids: jnp.ndarray,
                   n_buckets: int, cap: int, wp: dict) -> jnp.ndarray:
    """Sort-by-expert + capacity buffer + batched einsum over ``n_buckets``
    experts (ids >= n_buckets are drop buckets).  Returns (T, D).

    Deterministic drop policy: per expert, earliest-sorted tokens win a slot.
    """
    t, d = x2d.shape
    k = ids.shape[1]
    flat_ids = ids.reshape(-1)                                    # (T*k,)
    flat_w = w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)

    order = jnp.argsort(flat_ids, stable=True)
    sid = flat_ids[order]
    stok = flat_tok[order]
    sw = flat_w[order]

    # slot within expert = rank among same-expert entries (sorted order)
    first_idx = jnp.searchsorted(sid, jnp.arange(n_buckets), side="left")
    slot = jnp.arange(t * k) - first_idx[jnp.clip(sid, 0, n_buckets - 1)]
    keep = (slot < cap) & (sid < n_buckets)

    # scatter tokens into (E, C, D).  The (T*k, D) dispatch/return streams
    # and the capacity buffers' C axis are constrained over the DATA axes
    # (hints are no-ops outside a sharded launch): without them GSPMD
    # materializes ~25 GB unsharded gather temps per MoE layer.
    from repro.parallel.hints import shard_hint
    buf = shard_hint(jnp.zeros((n_buckets, cap, d), x2d.dtype), "moe_ecd")
    src = jnp.where(keep, stok, 0)
    gath = shard_hint(jnp.where(keep[:, None], x2d[src], 0).astype(x2d.dtype),
                      "moe_tkd")
    xe = buf.at[jnp.clip(sid, 0, n_buckets - 1),
                jnp.clip(slot, 0, cap - 1)].add(gath)
    xe = shard_hint(xe, "moe_ecd")

    ye = shard_hint(_expert_ffn(xe, wp), "moe_ecd")                # (E, C, D)

    # gather back with combine weights
    y_tok = shard_hint(
        ye[jnp.clip(sid, 0, n_buckets - 1), jnp.clip(slot, 0, cap - 1)],
        "moe_tkd")
    contrib = jnp.where(keep[:, None],
                        y_tok * sw[:, None].astype(y_tok.dtype), 0)
    return jnp.zeros((t, d), x2d.dtype).at[stok].add(contrib)


def moe_capacity(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                 capacity_factor: float = 1.25) -> jnp.ndarray:
    """Production path: sort-by-expert dispatch into (E, C, D) buffers.

    The buffers and expert batched-einsums carry ``moe_ecd`` sharding
    hints (expert axis over the model dim), so GSPMD partitions the
    expert compute (EP) instead of replicating 30 GB dispatch buffers and
    all-reducing them (§Perf iteration 3: 25 GB/device/layer of
    all-reduce traffic on deepseek-v2-lite prefill without the hints).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_per_token
    t = b * s
    x2d = x.reshape(t, d)
    w, ids = _routing(x2d, p["router"], k, cfg.norm_topk_prob)    # (T,k)
    cap = max(int(math.ceil(t * k / e * capacity_factor)), 1)
    y = _capacity_core(x2d, w, ids, e, cap, p)
    return y.reshape(b, s, d)


def moe_ep(x: jnp.ndarray, p: dict, cfg: ModelConfig, mesh, axis: str,
           capacity_factor: float = 1.25) -> jnp.ndarray:
    """Expert-parallel MoE: experts shard over ``axis`` (the mesh's model
    dimension), tokens stay sharded over the data axes, and dispatch runs
    fully locally inside a ``shard_map``:

      * each (data, model) shard routes its LOCAL tokens against the full
        router, keeps the assignments that land on its local E/n experts
        (others fall in a drop bucket), and runs the capacity path with
        per-data-shard capacity;
      * each shard's (T_local, D) contribution is stacked over the model
        axis and summed outside (one bf16 all-reduce per layer).

    This replaces the global argsort + unconstrained scatter/gather whose
    GSPMD lowering materializes (T*k, D) f32 tensors and all-reduces
    ~50 GB/device/layer on deepseek-v2-lite prefill (§Perf iteration 3).
    (A psum+replicated-out variant trips an XLA:CPU partitioner CHECK when
    nested in the layer scan; the stacked-partial form avoids it.)
    """
    from jax.sharding import PartitionSpec as P

    e, k = cfg.n_experts, cfg.n_experts_per_token
    n_shards = mesh.shape[axis]
    el = e // n_shards
    # NOTE: dispatching data-locally too (manual over the dp axes, per-shard
    # capacity) is numerically validated on a standalone 2x4 mesh, but JAX
    # 0.8.2 + XLA:CPU rejects dp-manual shard_map nested inside the layer
    # scan ("vma axes must be Manual") and hard-crashes the partitioner on
    # the psum variant — so this stays manual over the MODEL axis only;
    # tokens remain auto-sharded over dp.  See EXPERIMENTS.md §Perf iter 3.

    def body(xl, router, wg, wu, wd):
        b, s, d = xl.shape
        t = b * s
        x2d = xl.reshape(t, d)
        w, ids = _routing(x2d, router, k, cfg.norm_topk_prob)
        j = jax.lax.axis_index(axis)
        lo = j * el
        local = (ids >= lo) & (ids < lo + el)
        ids_l = jnp.where(local, ids - lo, el)          # bucket el = drop
        w_l = jnp.where(local, w, 0.0)
        cap = max(int(math.ceil(t * k / e * capacity_factor)), 1)
        # NOTE: the dp-axis hints inside _capacity_core stay ACTIVE here —
        # the data axis is auto inside this partial-manual region, and the
        # hints cut the dispatch bound ~30% (22s -> 15s memory+collective
        # on deepseek prefill).  They are only invalid under AD, and the
        # train path uses moe_impl="capacity" (no shard_map) instead.
        y = _capacity_core(x2d, w_l, ids_l, el, cap,
                           {"w_gate": wg, "w_up": wu, "w_down": wd})
        return y.astype(x.dtype).reshape(1, b, s, d)

    parts = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        axis_names={axis}, check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return jnp.sum(parts, axis=0)


# Token budget per dispatch chunk: bounds the (T*k, D) gather streams and
# (E, C, D) capacity buffers at prefill/train scale (1M global tokens would
# need ~100 GiB of dispatch temps).  Chunks re-stream expert weights, so
# keep them large.
MOE_CHUNK_TOKENS = 65536


def _chunked(fn, x: jnp.ndarray) -> jnp.ndarray:
    """Apply ``fn`` over sequence chunks of ~MOE_CHUNK_TOKENS tokens."""
    b, s, d = x.shape
    if b * s <= MOE_CHUNK_TOKENS:
        return fn(x)
    per_chunk = max(1, MOE_CHUNK_TOKENS // b)
    n = max(1, s // per_chunk)
    while s % n:
        n -= 1
    if n <= 1:
        return fn(x)
    cl = s // n
    xs = jnp.moveaxis(x.reshape(b, n, cl, d), 1, 0)      # (n, B, cl, D)
    # checkpoint per chunk: the backward otherwise stacks every chunk's
    # dispatch intermediates ((T_c*k, D) gathers x n chunks)
    ys = jax.lax.map(jax.checkpoint(fn), xs)
    return jnp.moveaxis(ys, 0, 1).reshape(b, s, d)


def _routed(x: jnp.ndarray, p: dict, cfg: ModelConfig,
            impl: str) -> jnp.ndarray:
    """The routed experts' part of the layer, by ``impl``."""
    from repro.parallel import hints

    b, s, d = x.shape
    if impl == "auto":
        ep = hints.ep_context()
        if (ep is not None and ep[0].shape[ep[1]] > 1
                and cfg.n_experts % ep[0].shape[ep[1]] == 0
                and cfg.n_experts >= ep[0].shape[ep[1]]):
            impl = "ep"
        elif b * s <= 4096 and cfg.n_experts <= 16:
            impl = "dense"
        else:
            impl = "capacity"
    if impl == "ep":
        ep = hints.ep_context()
        if ep is None:
            raise ValueError(
                "moe_impl='ep' needs an expert-parallel context "
                "(sharding_rules with a >1 model axis) and cannot nest "
                "inside an already-manual region (e.g. the TP serve "
                "shard_map, where experts run replicated); use impl='auto'")
        mesh, axis = ep
        return _chunked(lambda xc: moe_ep(xc, p, cfg, mesh, axis), x)
    if impl == "dense":
        return moe_dense(x, p, cfg)
    return _chunked(lambda xc: moe_capacity(xc, p, cfg), x)


def moe_forward(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                impl: str = "auto") -> jnp.ndarray:
    """Routed plus shared experts.  A held share (``n_experts_held``)
    always runs ``moe_share``, whatever ``impl`` asks for."""
    if cfg.n_experts_held:
        y = moe_share(x, p, cfg)
    else:
        y = _routed(x, p, cfg, impl)
    if cfg.n_shared_experts:
        y = y + common.swiglu(x, p["shared"]["w_gate"], p["shared"]["w_up"],
                              p["shared"]["w_down"])
    return y
