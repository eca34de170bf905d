"""Model assembly: config -> executable model (init / forward / prefill /
decode_step) for all assigned architecture families.

A model is a sequence of **segments**; each segment is a homogeneous run of
layers executed with ``jax.lax.scan`` over stacked parameters (O(1) HLO in
depth).  A segment step may contain several block kinds (e.g. Llama4's
alternating dense/MoE pair), so heterogeneous-period stacks still scan.
Layers that differ in attention window (Hymba's global/SWA mix) are split
into separate segments so the window — and hence the KV-cache geometry —
stays static per segment.

Block kinds:
  attn_dense   GQA attention + SwiGLU MLP            (qwen*, phi3, danube, hubert, internvl2 backbone)
  attn_moe     GQA attention + MoE                    (llama4-maverick)
  mla_dense    MLA attention + SwiGLU MLP             (deepseek first layer)
  mla_moe      MLA attention + MoE(+shared)           (deepseek)
  ssm          Mamba2 SSD mixer (no MLP)              (mamba2)
  hybrid       attention ∥ SSM heads, then MLP        (hymba)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers, moe as moe_lib, ssm as ssm_lib
from repro.models.attention_backends import backend_for_kind, layout_for_kind
from repro.models.common import (
    ModelConfig, count_params, dense_init, embed_init, rmsnorm, split_keys,
)
from repro.parallel.hints import shard_hint, tp_psum


@dataclasses.dataclass(frozen=True)
class Segment:
    kinds: tuple[str, ...]
    reps: int
    window: int | None = None     # attention window; None = full attention


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------


def build_plan(cfg: ModelConfig) -> list[Segment]:
    if cfg.family == "ssm":
        return [Segment(("ssm",), cfg.n_layers)]
    if cfg.family == "hybrid":
        # per-layer window: global attention at layers 0, every
        # ``global_attn_every``, and the last layer; SWA elsewhere.
        wins = []
        for i in range(cfg.n_layers):
            is_global = (cfg.global_attn_every and
                         (i % cfg.global_attn_every == 0 or i == cfg.n_layers - 1))
            wins.append(None if is_global else cfg.sliding_window)
        segs: list[Segment] = []
        for w in wins:
            if segs and segs[-1].window == w:
                segs[-1] = dataclasses.replace(segs[-1], reps=segs[-1].reps + 1)
            else:
                segs.append(Segment(("hybrid",), 1, w))
        return segs
    w = cfg.sliding_window
    if cfg.mla:
        segs = []
        nd = cfg.first_dense_layers
        if nd:
            segs.append(Segment(("mla_dense",), nd, w))
        segs.append(Segment(("mla_moe",), cfg.n_layers - nd, w))
        return segs
    if cfg.moe:
        if cfg.moe_layer_period == 1:
            segs = []
            nd = cfg.first_dense_layers
            if nd:
                segs.append(Segment(("attn_dense",), nd, w))
            segs.append(Segment(("attn_moe",), cfg.n_layers - nd, w))
            return segs
        assert cfg.n_layers % cfg.moe_layer_period == 0
        kinds = tuple(["attn_dense"] * (cfg.moe_layer_period - 1) + ["attn_moe"])
        return [Segment(kinds, cfg.n_layers // cfg.moe_layer_period, w)]
    return [Segment(("attn_dense",), cfg.n_layers, w)]


# ---------------------------------------------------------------------------
# Block dispatch
# ---------------------------------------------------------------------------


def _init_block(kind: str, key, cfg: ModelConfig) -> dict:
    ks = split_keys(key, 4)
    d = cfg.d_model
    ln = lambda: jnp.ones((d,), jnp.float32)
    be = backend_for_kind(kind)
    if kind in ("attn_dense", "mla_dense"):
        d_ff = cfg.d_ff if kind == "mla_dense" else None
        return {"ln1": ln(), "attn": be.init(ks[0], cfg),
                "ln2": ln(), "mlp": layers.init_mlp(ks[1], cfg, d_ff)}
    if kind in ("attn_moe", "mla_moe"):
        return {"ln1": ln(), "attn": be.init(ks[0], cfg),
                "ln2": ln(), "moe": moe_lib.init_moe(ks[1], cfg)}
    if kind == "ssm":
        return {"ln1": ln(), "ssm": ssm_lib.init_ssm(ks[0], cfg)}
    if kind == "hybrid":
        return {"ln1": ln(), "attn": be.init(ks[0], cfg),
                "ssm": ssm_lib.init_ssm(ks[1], cfg),
                "attn_out_norm": ln(), "ssm_out_norm": ln(),
                "ln2": ln(), "mlp": layers.init_mlp(ks[2], cfg)}
    raise ValueError(kind)


def _init_block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                      window: int | None, dtype=None):
    dtype = dtype or jnp.bfloat16
    be = backend_for_kind(kind)
    if kind == "ssm":
        return ssm_lib.init_ssm_state(cfg, batch)
    if kind == "hybrid":
        return {"attn": be.init_cache(cfg, batch, max_len, window,
                                      dtype=dtype),
                "ssm": ssm_lib.init_ssm_state(cfg, batch)}
    return be.init_cache(cfg, batch, max_len, window, dtype=dtype)


def _ffn(kind: str, p: dict, x, cfg: ModelConfig, moe_impl: str):
    if kind.endswith("_moe") or kind == "attn_moe":
        # inside a manual TP serve region MoE weights are replicated —
        # every expert matmul (incl. shared experts) is already complete,
        # so the whole subtree traces with the Megatron marks off
        from repro.parallel.hints import no_manual_tp
        with no_manual_tp():
            return moe_lib.moe_forward(x, p["moe"], cfg, impl=moe_impl)
    return layers.mlp_forward(p["mlp"], x)


def _block_forward(kind: str, p: dict, x, cfg: ModelConfig, window,
                   moe_impl: str):
    be = backend_for_kind(kind)
    if kind == "ssm":
        out, _ = ssm_lib.ssm_forward(rmsnorm(x, p["ln1"], cfg.norm_eps), p["ssm"], cfg)
        return x + out
    if kind == "hybrid":
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        a = be.forward(p["attn"], h, cfg, window=window)
        s, _ = ssm_lib.ssm_forward(h, p["ssm"], cfg)
        mix = 0.5 * (rmsnorm(a, p["attn_out_norm"], cfg.norm_eps)
                     + rmsnorm(s, p["ssm_out_norm"], cfg.norm_eps))
        x = x + mix
        x = x + layers.mlp_forward(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps))
        return x
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    a = be.forward(p["attn"], h, cfg, window=window)
    x = x + a
    x = x + _ffn(kind, p, rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, moe_impl)
    return shard_hint(x, "act_bsd")


def _block_prefill(kind: str, p: dict, x, cfg: ModelConfig, window, cache,
                   moe_impl: str):
    be = backend_for_kind(kind)
    if kind == "ssm":
        out, st = ssm_lib.ssm_forward(rmsnorm(x, p["ln1"], cfg.norm_eps),
                                      p["ssm"], cfg, None)
        return x + out, st
    if kind == "hybrid":
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        a, ac = be.prefill(p["attn"], h, cfg, cache["attn"], window=window)
        s, sc = ssm_lib.ssm_forward(h, p["ssm"], cfg, None)
        mix = 0.5 * (rmsnorm(a, p["attn_out_norm"], cfg.norm_eps)
                     + rmsnorm(s, p["ssm_out_norm"], cfg.norm_eps))
        x = x + mix
        x = x + layers.mlp_forward(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps))
        return x, {"attn": ac, "ssm": sc}
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    a, c = be.prefill(p["attn"], h, cfg, cache, window=window)
    x = x + a
    x = x + _ffn(kind, p, rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, moe_impl)
    return x, c


def _init_block_page_pool(kind: str, cfg: ModelConfig, num_pages: int,
                          page_size: int, dtype=None):
    dtype = dtype or jnp.bfloat16
    be = backend_for_kind(kind)
    if be is None:
        # pure-state kinds (ssm) write no token-indexed pages: an empty
        # pool keeps the pytree structure parallel so the scanned segment
        # protocol (and the engine's page walkers) need no special case
        return {}
    if not be.supports_paged:
        raise NotImplementedError(
            f"continuous batching: no paged cache for block kind {kind!r}")
    pool = be.init_page_pool(cfg, num_pages, page_size, dtype=dtype)
    # quantized pools may carry extra metadata leaves (k_scale/v_scale)
    # beyond the declared token-axis leaves
    assert set(be.paged_leaf_keys) <= set(pool), \
        (f"backend {be.name!r} pool layout {sorted(pool)} missing declared "
         f"paged_leaf_keys {sorted(be.paged_leaf_keys)}")
    return pool


def _gather_state_rows(state, slot_idx, start):
    """Pick per-slot state rows for a prefill chunk's bucket rows.

    Rows whose chunk starts at position 0 read a ZERO state in-graph:
    admission and preemption-restart both begin at ``start == 0``, so the
    host never has to reset state-pool rows between tenants — the zeroing
    is part of the traced step, like the scratch-page redirect for pages."""
    def pick(a):
        rows = a[slot_idx]
        fresh = (start == 0).reshape((-1,) + (1,) * (rows.ndim - 1))
        return jnp.where(fresh, jnp.zeros_like(rows), rows)
    return jax.tree.map(pick, state)


def _scatter_state_rows(state, rows, slot_idx, valid):
    """Write updated rows back into the slot-indexed pool; bucket padding
    rows (``valid == 0``) are dropped via an out-of-bounds index."""
    def put(a, r):
        safe = jnp.where(valid > 0, slot_idx, a.shape[0])
        return a.at[safe].set(r.astype(a.dtype), mode="drop")
    return jax.tree.map(put, state, rows)


def _commit_state_rows(state, new, ok):
    """Decode-step commit: only rows actually decoding this step replace
    their state (other slots may be mid-prefill in the same iteration)."""
    def put(a, n):
        m = ok.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, n.astype(a.dtype), a)
    return jax.tree.map(put, state, new)


def _block_decode_paged(kind: str, p: dict, x, cfg: ModelConfig, window,
                        pool, page_table, pos, moe_impl: str,
                        state=None, state_ok=None, layer=None):
    """Paged analogue of ``_block_decode``: per-slot ragged positions and
    K/V streamed through the page table.  x: (B, D).

    Stateful kinds (ssm, the SSM half of hybrid) run the exact
    single-token recurrence over their slot-indexed ``state`` rows and
    commit only rows flagged by ``state_ok`` (slots actually decoding).
    Returns ``(x, new_pool, new_state)`` — stateless kinds pass their
    (possibly empty) state through untouched.  ``layer``: this block's
    index into a layer-stacked ``pool`` (see ``attention_backends``).

    The ``tp_psum`` marks close the Megatron column->row pairs when this
    traces inside the sharded serve path's manual region (one reduction
    per attention block, one per dense MLP; MoE experts run replicated
    there, so their output is already complete).  Off-mesh they are
    identity."""
    be = backend_for_kind(kind)
    if kind == "ssm":
        out, st = ssm_lib.ssm_decode_step(rmsnorm(x, p["ln1"], cfg.norm_eps),
                                          p["ssm"], cfg, state)
        return x + out, pool, _commit_state_rows(state, st, state_ok)
    if kind == "hybrid":
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        a, c = be.decode_paged(p["attn"], h, cfg, pool, page_table, pos,
                               window=window, layer=layer)
        s, st = ssm_lib.ssm_decode_step(h, p["ssm"], cfg, state)
        mix = 0.5 * (rmsnorm(a, p["attn_out_norm"], cfg.norm_eps)
                     + rmsnorm(s, p["ssm_out_norm"], cfg.norm_eps))
        x = x + mix
        x = x + layers.mlp_forward(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps))
        return x, c, _commit_state_rows(state, st, state_ok)
    if be is None or be.decode_paged is None:
        raise NotImplementedError(kind)
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    a, c = be.decode_paged(p["attn"], h, cfg, pool, page_table, pos,
                           window=window, layer=layer)
    x = x + tp_psum(a).astype(x.dtype)
    f = _ffn(kind, p, rmsnorm(x[:, None, :], p["ln2"], cfg.norm_eps), cfg,
             moe_impl)[:, 0]
    x = x + (f if kind.endswith("_moe") else tp_psum(f).astype(x.dtype))
    return x, c, state


def _block_prefill_chunk_paged(kind: str, p: dict, x, cfg: ModelConfig,
                               window, pool, page_table, start, valid,
                               moe_impl: str, state=None, slot_idx=None,
                               layer=None):
    """Paged chunked-prefill analogue of ``_block_prefill``.  x: (B, C, D);
    start/valid: (B,) per-slot chunk offset and real-token count.

    Stateful kinds gather their ``slot_idx`` state rows (zeroed at
    ``start == 0``), run the chunked SSD with ``valid`` masking so the
    carried state lands exactly at the valid boundary, and scatter the
    rows back.  Returns ``(x, new_pool, new_state)``."""
    be = backend_for_kind(kind)
    if kind == "ssm":
        rows = _gather_state_rows(state, slot_idx, start)
        out, st = ssm_lib.ssm_forward(rmsnorm(x, p["ln1"], cfg.norm_eps),
                                      p["ssm"], cfg, rows, valid=valid)
        return x + out, pool, _scatter_state_rows(state, st, slot_idx, valid)
    if kind == "hybrid":
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        a, c = be.prefill_chunk_paged(p["attn"], h, cfg, pool, page_table,
                                      start, valid, window=window,
                                      layer=layer)
        rows = _gather_state_rows(state, slot_idx, start)
        s, st = ssm_lib.ssm_forward(h, p["ssm"], cfg, rows, valid=valid)
        mix = 0.5 * (rmsnorm(a, p["attn_out_norm"], cfg.norm_eps)
                     + rmsnorm(s, p["ssm_out_norm"], cfg.norm_eps))
        x = x + mix
        x = x + layers.mlp_forward(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps))
        return x, c, _scatter_state_rows(state, st, slot_idx, valid)
    if be is None or be.prefill_chunk_paged is None:
        raise NotImplementedError(kind)
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    a, c = be.prefill_chunk_paged(p["attn"], h, cfg, pool, page_table, start,
                                  valid, window=window, layer=layer)
    x = x + tp_psum(a).astype(x.dtype)
    f = _ffn(kind, p, rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, moe_impl)
    x = x + (f if kind.endswith("_moe") else tp_psum(f).astype(x.dtype))
    return x, c, state


def _block_decode_multi_paged(kind: str, p: dict, x, cfg: ModelConfig,
                              window, pool, page_table, start, valid,
                              moe_impl: str, layer=None):
    """Multi-token paged decode (speculative verify): x: (B, C, D) chosen
    tokens at per-slot offsets ``start`` with ``valid`` real rows.  Same
    block shape as ``_block_prefill_chunk_paged`` but dispatched through
    the backend's ``decode_multi_paged`` entry so new cache families can
    split the two paths (e.g. SSM states need an explicit multi-step
    scan here but a one-shot conv prefill there)."""
    be = backend_for_kind(kind)
    if be is None or be.decode_multi_paged is None or kind == "hybrid":
        raise NotImplementedError(
            f"multi-token decode (speculative verify) over block kind "
            f"{kind!r}: state pools advance one token per step — the "
            f"engine gates speculation off for stateful layouts")
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    a, c = be.decode_multi_paged(p["attn"], h, cfg, pool, page_table, start,
                                 valid, window=window, layer=layer)
    x = x + tp_psum(a).astype(x.dtype)
    f = _ffn(kind, p, rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, moe_impl)
    x = x + (f if kind.endswith("_moe") else tp_psum(f).astype(x.dtype))
    return x, c


def _only_layer(stack):
    """A one-layer segment's parameters without their layer axis."""
    return jax.tree.map(lambda a: a[0], stack)


def _run_segment(seg: Segment, block, x, stack, pools, states):
    """Run one segment's paged blocks: ``block(kind, p, x, pool, state,
    layer) -> (x, pool, state)`` for every kind of every repetition.

    A scanned segment (``reps > 1``) carries its layer-stacked pools
    through the scan and hands each block the whole stack plus its layer
    index: the blocks write their new K/V into the stack and read it in
    place.  (As scan inputs and outputs the pools would be re-stacked
    into a second buffer — twice the pool's bytes live at once, and the
    whole pool copied every step.)  Per-slot recurrent ``states`` are
    small and stay scan inputs/outputs.  Returns ``(x, pools, states,
    hits)``; ``states`` is None in and out for stateless calls, and
    ``hits`` stacks the ``moe.expert_hits`` of the segment's expert-share
    layers (None where it has none)."""
    kinds = seg.kinds
    no_state = ({},) * len(kinds)

    def seg_step(carry, ps, ss, li):
        xc, cs = carry
        new_cs, new_ss = [], []
        with moe_lib.expert_hits() as hits:
            for kind, p, c, s in zip(kinds, ps, cs, no_state if ss is None
                                     else ss):
                xc, nc, ns = block(kind, p, xc, c, s, li)
                new_cs.append(nc)
                new_ss.append(ns)
        return (xc, tuple(new_cs)), (None if ss is None else tuple(new_ss),
                                     jnp.stack(hits) if hits else None)

    if seg.reps == 1:
        (x, pools), (states, hits) = seg_step((x, pools), _only_layer(stack),
                                              states, None)
        return x, pools, states, hits
    (x, pools), (states, hits) = jax.lax.scan(
        lambda carry, xs: seg_step(carry, *xs), (x, pools),
        (stack, states, jnp.arange(seg.reps, dtype=jnp.int32)))
    if hits is not None:                 # (reps, kinds, T, E) -> layers
        hits = hits.reshape((-1,) + hits.shape[2:])
    return x, pools, states, hits


def _block_decode(kind: str, p: dict, x, cfg: ModelConfig, window, cache,
                  cur_pos, moe_impl: str):
    """x: (B, D) single-token representations."""
    be = backend_for_kind(kind)
    if kind == "ssm":
        out, st = ssm_lib.ssm_decode_step(rmsnorm(x, p["ln1"], cfg.norm_eps),
                                          p["ssm"], cfg, cache)
        return x + out, st
    if kind == "hybrid":
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        a, ac = be.decode(p["attn"], h, cfg, cache["attn"], cur_pos,
                          window=window)
        s, sc = ssm_lib.ssm_decode_step(h, p["ssm"], cfg, cache["ssm"])
        mix = 0.5 * (rmsnorm(a, p["attn_out_norm"], cfg.norm_eps)
                     + rmsnorm(s, p["ssm_out_norm"], cfg.norm_eps))
        x = x + mix
        x = x + layers.mlp_forward(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps))
        return x, {"attn": ac, "ssm": sc}
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    a, c = be.decode(p["attn"], h, cfg, cache, cur_pos, window=window)
    x = x + a
    x = x + _ffn(kind, p, rmsnorm(x[:, None, :], p["ln2"], cfg.norm_eps), cfg,
                 moe_impl)[:, 0]
    return x, c


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class Model:
    """Executable model for one ``ModelConfig``.

    Stateless: all state lives in explicit ``params`` / ``cache`` pytrees.
    """

    def __init__(self, cfg: ModelConfig, moe_impl: str = "auto"):
        self.cfg = cfg
        self.plan = build_plan(cfg)
        self.moe_impl = moe_impl
        # stateful serving: any kind carrying per-slot recurrent state
        self._needs_state = any(layout_for_kind(k).state
                                for seg in self.plan for k in seg.kinds)
        assert sum(len(s.kinds) * s.reps for s in self.plan) == cfg.n_layers
        for seg in self.plan:               # windowed segments need a
            for kind in seg.kinds:          # sliding-capable dense backend
                be = backend_for_kind(kind)
                if seg.window is not None and be is not None:
                    assert "sliding" in be.mask_families, \
                        (f"backend {be.name!r} has no sliding mask for "
                         f"windowed segment kind {kind!r}")

    # ----- init -----
    def init(self, key) -> dict:
        cfg = self.cfg
        keys = split_keys(key, len(self.plan) + 3)
        stacks = []
        for seg, k in zip(self.plan, keys[:-3]):
            kinds_params = []
            for ki, kind in enumerate(seg.kinds):
                kk = jax.random.fold_in(k, ki)
                # every segment's parameters carry a leading layer axis,
                # a one-layer segment's too (it runs unscanned)
                if seg.reps == 1:
                    kinds_params.append(jax.tree.map(
                        lambda a: a[None], _init_block(kind, kk, cfg)))
                else:
                    kinds_params.append(jax.vmap(
                        lambda kkk: _init_block(kind, kkk, cfg))(
                            jax.random.split(kk, seg.reps)))
            stacks.append(tuple(kinds_params))
        params: dict[str, Any] = {"stacks": stacks,
                                  "final_norm": jnp.ones((cfg.d_model,), jnp.float32)}
        if cfg.frontend == "audio":
            params["in_proj"] = dense_init(keys[-3], cfg.d_model, cfg.d_model)
            params["head"] = dense_init(keys[-2], cfg.d_model, cfg.padded_vocab)
        else:
            params["embed"] = embed_init(keys[-3], cfg.padded_vocab, cfg.d_model)
            if not cfg.tie_embeddings:
                params["head"] = dense_init(keys[-2], cfg.d_model, cfg.padded_vocab)
        return params

    # ----- shared pieces -----
    def _embed_inputs(self, params: dict, batch: dict) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.frontend == "audio":
            x = batch["features"].astype(jnp.bfloat16) @ params["in_proj"]
        elif cfg.frontend == "vision":
            tok = params["embed"][batch["tokens"]]
            x = jnp.concatenate([batch["image_embeds"].astype(tok.dtype), tok],
                                axis=1)
        else:
            x = params["embed"][batch["tokens"]]
        return shard_hint(x, "act_bsd")

    def _head(self, params: dict, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed"].T
        else:
            logits = x @ params["head"]
        if cfg.padded_vocab != cfg.vocab_size:   # mask pad columns to -inf
            pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad, jnp.asarray(-1e30, logits.dtype), logits)
        return shard_hint(logits, "logits")

    # ----- forward (training / no-cache prefill) -----
    def forward(self, params: dict, batch: dict, *, remat: bool = False) -> jnp.ndarray:
        cfg = self.cfg
        x = self._embed_inputs(params, batch)

        for si, seg in enumerate(self.plan):
            stack = params["stacks"][si]

            def seg_step(xc, ps, seg=seg):
                for kind, p in zip(seg.kinds, ps):
                    xc = _block_forward(kind, p, xc, cfg, seg.window,
                                        self.moe_impl)
                return xc

            if remat:
                # Save ONLY the scan carry (layer boundary); recompute all
                # within-layer activations on the backward pass.  At 4k x 256
                # x 40L saving dot outputs too would need >100 GiB/device.
                seg_step = jax.checkpoint(seg_step)

            if seg.reps == 1:
                x = seg_step(x, _only_layer(stack))
            else:
                x, _ = jax.lax.scan(lambda c, ps: (seg_step(c, ps), None),
                                    x, stack)
        return self._head(params, x)

    # ----- loss -----
    @staticmethod
    def _xent(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
        """Mean cross-entropy without materializing (B,S,V) log-probs.

        ``logsumexp`` and ``take_along_axis`` reduce the vocab axis in f32
        on the fly, so the only (B,S,V) buffer is the bf16 logits (which
        shard over TP via the "logits" rule) — essential for 200k-vocab
        training cells.
        """
        lf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        tgt = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - tgt)

    def loss(self, params: dict, batch: dict, *, remat: bool = False) -> jnp.ndarray:
        cfg = self.cfg
        logits = self.forward(params, batch, remat=remat)
        if cfg.frontend == "audio":
            return self._xent(logits, batch["labels"])
        tokens = batch["tokens"]
        if cfg.frontend == "vision":
            ni = batch["image_embeds"].shape[1]
            logits = logits[:, ni:, :]
        return self._xent(logits[:, :-1], tokens[:, 1:])

    # ----- cache -----
    def init_cache(self, batch: int, max_len: int, dtype=None) -> list:
        cfg = self.cfg
        caches = []
        for seg in self.plan:
            kinds_caches = []
            for kind in seg.kinds:
                single = _init_block_cache(kind, cfg, batch, max_len,
                                           seg.window, dtype)
                if seg.reps == 1:
                    kinds_caches.append(single)
                else:
                    kinds_caches.append(jax.tree.map(
                        lambda a: jnp.tile(a[None], (seg.reps,) + (1,) * a.ndim),
                        single))
            caches.append(tuple(kinds_caches))
        return caches

    # ----- paged cache (continuous-batching serve) -----
    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=None, *, ring_pages: int | None = None) -> list:
        """Physical page pools, one per layer, in the same nested structure
        as ``init_cache`` (list over segments, tuple over kinds, stacked
        along a leading reps axis for scanned segments).  All full-KV
        layers share one logical page-id space — the allocator in
        ``runtime.kv_cache`` is model-agnostic.

        ``ring_pages``: pool size for sliding-window segments, which live
        in their own (smaller) page-id space managed by
        ``runtime.state_cache.RingPageSpace`` — O(window) pages per slot
        instead of O(context).  When None (legacy callers), windowed
        segments share the full space and simply never reclaim."""
        cfg = self.cfg
        pools = []
        for seg in self.plan:
            if seg.window is not None and any(
                    (be := backend_for_kind(k)) is None
                    or "sliding" not in be.paged_mask_families
                    for k in seg.kinds):
                raise NotImplementedError(
                    "continuous batching over sliding-window segments needs "
                    "a sliding-capable paged backend")
            size = (ring_pages if (seg.window is not None
                                   and ring_pages is not None) else num_pages)
            kinds_pools = []
            for kind in seg.kinds:
                single = _init_block_page_pool(kind, cfg, size,
                                               page_size, dtype)
                if seg.reps == 1:
                    kinds_pools.append(single)
                else:
                    kinds_pools.append(jax.tree.map(
                        lambda a: jnp.tile(a[None], (seg.reps,) + (1,) * a.ndim),
                        single))
            pools.append(tuple(kinds_pools))
        return pools

    def init_state_pools(self, num_slots: int) -> list:
        """Per-slot recurrent state pools (SSM conv tail + SSD state), in
        the same nested structure as ``init_paged_cache``; stateless kinds
        get empty subtrees so the scanned-segment protocol is uniform."""
        cfg = self.cfg
        states = []
        for seg in self.plan:
            kinds_states = []
            for kind in seg.kinds:
                lay = layout_for_kind(kind)
                single = (lay.init_state_pool(cfg, num_slots)
                          if lay.state else {})
                if seg.reps == 1:
                    kinds_states.append(single)
                else:
                    kinds_states.append(jax.tree.map(
                        lambda a: jnp.tile(a[None], (seg.reps,) + (1,) * a.ndim),
                        single))
            states.append(tuple(kinds_states))
        return states

    def prefill_chunk_paged(self, params: dict, tokens: jnp.ndarray,
                            pools: list, page_table: jnp.ndarray,
                            start: jnp.ndarray, valid: jnp.ndarray, *,
                            states: list | None = None,
                            ring_table: jnp.ndarray | None = None,
                            slot_idx: jnp.ndarray | None = None):
        """One fixed-size prefill chunk over a slot batch, straight into the
        page pools.

        tokens: (B, C) int32 chunk tokens (rows padded past ``valid``);
        start: (B,) int32 absolute position of tokens[:, 0]; valid: (B,)
        int32 number of real tokens in each row (0 for padding rows, whose
        page-table rows must point at the scratch page).  Each chunk
        attends over the pages already written for its slot — earlier
        chunks, or prefix-cache pages shared from another request — so long
        prompts prefill incrementally, interleaved with decode iterations.

        Stateful models additionally thread ``states`` (slot-indexed
        pools from ``init_state_pools``) with ``slot_idx`` (B,) mapping
        bucket rows to slots, and ``ring_table`` for sliding-window
        segments; the return gains a third element, the updated states.

        Returns per-row logits at the row's last valid position (the
        first-token logits once a request's final chunk lands) and the
        updated pools."""
        x, new_pools, new_states = self._prefill_chunk_body(
            params, tokens, pools, page_table, start, valid,
            states=states, ring_table=ring_table, slot_idx=slot_idx)
        b, c = tokens.shape
        last = jnp.clip(valid - 1, 0, c - 1)
        x_last = x[jnp.arange(b), last]
        logits = self._head(params, x_last[:, None, :])[:, 0]
        if states is None:
            return logits, new_pools
        return logits, new_pools, new_states

    def prefill_chunk_scored_paged(self, params: dict, tokens: jnp.ndarray,
                                   pools: list, page_table: jnp.ndarray,
                                   start: jnp.ndarray, valid: jnp.ndarray, *,
                                   states: list | None = None,
                                   ring_table: jnp.ndarray | None = None,
                                   slot_idx: jnp.ndarray | None = None):
        """Chunked paged prefill that also SCORES the chunk (prompt
        logprobs): returns (last_logits (B, V), full_logits (B, C, V),
        pools[, states]).  ``last_logits`` comes through exactly the same
        last-position head shape as ``prefill_chunk_paged``, so a scored
        admission samples the identical first token; ``full_logits`` feed
        raw prompt-token scoring, where rounding parity doesn't matter."""
        x, new_pools, new_states = self._prefill_chunk_body(
            params, tokens, pools, page_table, start, valid,
            states=states, ring_table=ring_table, slot_idx=slot_idx)
        b, c = tokens.shape
        last = jnp.clip(valid - 1, 0, c - 1)
        x_last = x[jnp.arange(b), last]
        last_logits = self._head(params, x_last[:, None, :])[:, 0]
        if states is None:
            return last_logits, self._head(params, x), new_pools
        return last_logits, self._head(params, x), new_pools, new_states

    def _prefill_chunk_body(self, params, tokens, pools, page_table, start,
                            valid, states=None, ring_table=None,
                            slot_idx=None):
        cfg = self.cfg
        assert cfg.frontend is None, "chunked paged prefill serves tokens only"
        if states is None and self._needs_state:
            raise NotImplementedError(
                f"{cfg.name}: ssm/hybrid serving needs per-slot state pools "
                f"— pass states=init_state_pools(num_slots) (the continuous "
                f"engine threads them automatically)")
        x = params["embed"][tokens]                        # (B, C, D)
        x = shard_hint(x, "act_bsd")
        new_pools = []
        new_states = [] if states is not None else None
        for si, seg in enumerate(self.plan):
            # sliding-window segments index their own (ring) page space
            tbl = (ring_table if (seg.window is not None
                                  and ring_table is not None) else page_table)

            def block(kind, p, xc, c, s, li, seg=seg, tbl=tbl):
                return _block_prefill_chunk_paged(
                    kind, p, xc, cfg, seg.window, c, tbl, start, valid,
                    self.moe_impl, state=s, slot_idx=slot_idx, layer=li)

            x, nc, ns, _ = _run_segment(
                seg, block, x, params["stacks"][si], pools[si],
                None if states is None else states[si])
            new_pools.append(nc)
            if states is not None:
                new_states.append(ns)
        return x, new_pools, new_states

    def decode_step_paged(self, params: dict, tokens: jnp.ndarray,
                          pools: list, page_table: jnp.ndarray,
                          pos: jnp.ndarray, valid: jnp.ndarray | None = None,
                          *, states: list | None = None,
                          ring_table: jnp.ndarray | None = None,
                          state_ok: jnp.ndarray | None = None,
                          expert_hits: bool = False):
        """One continuous-batching decode step over the slot batch.

        tokens: (B,) int32 (one per slot); pos: (B,) int32 per-slot ragged
        positions; page_table: (B, n_blocks) int32.  Inactive slots point
        at the scratch page and are masked out by the caller.

        Stateful models thread ``states`` (slot-indexed pools, B ==
        num_slots rows aligned with the decode batch), ``ring_table``
        (the sliding-window segments' own page space), and ``state_ok``
        (B,) bool marking slots actually decoding (their state rows
        commit; all other rows keep their value).  The return gains a
        third element, the updated states.  With ``expert_hits`` it gains a
        last one: the ``(MoE layers, B, held experts)`` bool matrix of the
        rows each held expert took (``moe.expert_hits``), or None for a
        model without an expert share.

        Multi-token form (speculative verify / prompt scoring): tokens
        (B, C) int32 of C *already-chosen* tokens per slot starting at
        per-slot position ``pos`` with ``valid`` (B,) real rows (the rest
        scatter to the scratch page) — returns (B, C, V) logits, one
        next-token distribution per fed position, through the backends'
        ``decode_multi_paged`` ragged-q_offset path (unsupported for
        stateful layouts — speculation is gated off there)."""
        cfg = self.cfg
        assert cfg.frontend != "audio", "encoder-only models have no decode step"
        if tokens.ndim == 2:
            if states is not None:
                raise NotImplementedError(
                    "multi-token decode over state pools (speculative "
                    "verify) is unsupported — the engine gates it off")
            return self._decode_multi_paged(params, tokens, pools, page_table,
                                            pos, valid)
        if states is None and self._needs_state:
            raise NotImplementedError(
                f"{cfg.name}: ssm/hybrid serving needs per-slot state pools "
                f"— pass states=init_state_pools(num_slots) (the continuous "
                f"engine threads them automatically)")
        x = params["embed"][tokens]
        x = shard_hint(x, "act_bd")
        new_pools, hits = [], []
        new_states = [] if states is not None else None
        for si, seg in enumerate(self.plan):
            tbl = (ring_table if (seg.window is not None
                                  and ring_table is not None) else page_table)

            def block(kind, p, xc, c, s, li, seg=seg, tbl=tbl):
                return _block_decode_paged(
                    kind, p, xc, cfg, seg.window, c, tbl, pos, self.moe_impl,
                    state=s, state_ok=state_ok, layer=li)

            x, nc, ns, h = _run_segment(
                seg, block, x, params["stacks"][si], pools[si],
                None if states is None else states[si])
            new_pools.append(nc)
            if states is not None:
                new_states.append(ns)
            if h is not None:
                hits.append(h)
        logits = self._head(params, x[:, None, :])[:, 0]
        out = (logits, new_pools)
        if states is not None:
            out += (new_states,)
        if expert_hits:
            out += (jnp.concatenate(hits) if hits else None,)
        return out

    def _decode_multi_paged(self, params: dict, tokens: jnp.ndarray,
                            pools: list, page_table: jnp.ndarray,
                            pos: jnp.ndarray, valid: jnp.ndarray | None
                            ) -> tuple[jnp.ndarray, list]:
        """(B, C) tokens at per-slot offsets -> (B, C, V) logits; the head
        keeps EVERY position (the verify step scores all gamma+1 of them),
        unlike chunked prefill's last-valid-only head.

        On CPU the window is flattened into B*C VIRTUAL SLOTS and run
        through the single-token decode program itself: each window token
        becomes its own decode row with its own position and a copy of its
        slot's page-table row, so every position's logits — and every KV
        write — come out of literally the same compiled computation as
        the non-speculative decode step, bit for bit (the greedy
        byte-identity contract; a chunk-shaped (B, C, D) trace diverges at
        bf16 ulp inside the scanned segments because XLA fuses the 3-D
        carry differently).  Later window positions ARE already scattered
        when an earlier query reads the pool, but the causal ``idx <=
        pos`` mask assigns them exp(NEG_INF) == exact zero weight, which
        is indistinguishable from their never having been written.  On
        accelerators the chunk-shaped ``decode_multi_paged`` dispatch
        runs instead: pages stream once per slot (not once per window
        token), and the byte-contract doesn't span kernels there anyway.
        """
        from repro.kernels import on_cpu

        b, c = tokens.shape
        if valid is None:
            valid = jnp.full((b,), c, jnp.int32)
        if on_cpu():
            ok = (jnp.arange(c)[None, :] < valid[:, None]).reshape(b * c)
            vpt = jnp.where(ok[:, None],
                            jnp.repeat(page_table, c, axis=0), 0)
            vpos = (jnp.repeat(pos, c)
                    + jnp.tile(jnp.arange(c, dtype=pos.dtype), b))
            vpos = jnp.where(ok, vpos, 0)
            logits, new_pools = self.decode_step_paged(
                params, tokens.reshape(b * c), pools, vpt, vpos)
            return logits.reshape(b, c, -1), new_pools
        x = params["embed"][tokens]                        # (B, C, D)
        x = shard_hint(x, "act_bsd")
        new_pools = []
        for si, seg in enumerate(self.plan):

            def block(kind, p, xc, c, s, li, seg=seg):
                xc, nc = _block_decode_multi_paged(
                    kind, p, xc, self.cfg, seg.window, c, page_table, pos,
                    valid, self.moe_impl, layer=li)
                return xc, nc, s

            x, nc, _, _ = _run_segment(seg, block, x, params["stacks"][si],
                                       pools[si], None)
            new_pools.append(nc)
        logits = self._head(params, x)                     # (B, C, V)
        return logits, new_pools

    # ----- prefill -----
    def prefill(self, params: dict, batch: dict, cache: list):
        """Run the full prompt, fill the cache; returns (last_logits, cache)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        new_caches = []
        for si, seg in enumerate(self.plan):
            stack = params["stacks"][si]

            def seg_step(xc, layer, seg=seg):
                ps, cs = layer
                new_cs = []
                for kind, p, c in zip(seg.kinds, ps, cs):
                    xc, nc = _block_prefill(kind, p, xc, cfg, seg.window, c,
                                            self.moe_impl)
                    new_cs.append(nc)
                return xc, tuple(new_cs)

            if seg.reps == 1:
                x, nc = seg_step(x, (_only_layer(stack), cache[si]))
            else:
                x, nc = jax.lax.scan(seg_step, x, (stack, cache[si]))
            new_caches.append(nc)
        logits = self._head(params, x[:, -1:, :])[:, 0]
        return logits, new_caches

    # ----- decode -----
    def decode_step(self, params: dict, tokens: jnp.ndarray, cache: list,
                    cur_pos) -> tuple[jnp.ndarray, list]:
        """One decode step.  tokens: (B,) int32; cur_pos: scalar position."""
        cfg = self.cfg
        assert cfg.frontend != "audio", "encoder-only models have no decode step"
        x = params["embed"][tokens]
        x = shard_hint(x, "act_bd")
        new_caches = []
        for si, seg in enumerate(self.plan):
            stack = params["stacks"][si]

            def seg_step(xc, layer, seg=seg):
                ps, cs = layer
                new_cs = []
                for kind, p, c in zip(seg.kinds, ps, cs):
                    xc, nc = _block_decode(kind, p, xc, cfg, seg.window, c,
                                           cur_pos, self.moe_impl)
                    new_cs.append(nc)
                return xc, tuple(new_cs)

            if seg.reps == 1:
                x, nc = seg_step(x, (_only_layer(stack), cache[si]))
            else:
                x, nc = jax.lax.scan(seg_step, x, (stack, cache[si]))
            new_caches.append(nc)
        logits = self._head(params, x[:, None, :])[:, 0]
        return logits, new_caches

    def param_count(self, params) -> int:
        return count_params(params)


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
