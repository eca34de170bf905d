"""Logical sharding hints — decouples model code from mesh layout.

Model layers call ``shard_hint(x, "act_btd")`` at layer boundaries; the
launcher installs a rules table mapping logical names to
``PartitionSpec``s for the active mesh (see ``parallel.plan``).  Outside a
rules context the hints are no-ops, so models stay pure single-device code
for CPU tests.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Mapping

import jax

_RULES: contextvars.ContextVar[Mapping | None] = contextvars.ContextVar(
    "shard_rules", default=None)


@contextlib.contextmanager
def sharding_rules(rules: Mapping):
    """Install logical-name -> PartitionSpec rules for the enclosed trace."""
    token = _RULES.set(rules)
    try:
        yield
    finally:
        _RULES.reset(token)


def _drop_uneven(sharding, shape):
    """Drop sharded axes on dims the array size doesn't divide (e.g. 25
    heads over a 16-way model axis) — the hint then constrains only the
    dims that partition cleanly."""
    from jax.sharding import NamedSharding, PartitionSpec
    if not isinstance(sharding, NamedSharding):
        return sharding
    mesh = sharding.mesh
    spec = sharding.spec
    new = []
    changed = False
    for dim in range(len(shape)):
        entry = spec[dim] if dim < len(spec) else None
        if entry is None:
            new.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        if shape[dim] % prod != 0:
            new.append(None)
            changed = True
        else:
            new.append(entry)
    if not changed:
        return sharding
    return NamedSharding(mesh, PartitionSpec(*new))


_SUSPENDED: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "shard_hints_suspended", default=False)


@contextlib.contextmanager
def suspend_hints():
    """Disable shard hints for the enclosed trace — used inside shard_map
    manual regions, where constraints built from the launcher's (all-Auto)
    mesh are invalid and break the backward pass."""
    token = _SUSPENDED.set(True)
    try:
        yield
    finally:
        _SUSPENDED.reset(token)


def _in_manual_region() -> bool:
    return _SUSPENDED.get()


def _rebuild_for_context(sharding):
    """Rebuild the rule's NamedSharding against the ambient abstract mesh.

    Inside a partial-manual shard_map region the context mesh marks some
    axes Manual; a constraint built from the launcher's all-Auto Mesh is
    rejected (including by the backward pass).  Keep only spec axes that
    are Auto in the ambient mesh and bind the spec to that mesh.
    """
    from jax.sharding import AxisType, NamedSharding, PartitionSpec
    am = jax.sharding.get_abstract_mesh()
    if tuple(am.axis_names) != tuple(sharding.mesh.axis_names):
        return sharding
    manual = {a for a, t in zip(am.axis_names, am.axis_types)
              if t == AxisType.Manual}
    if not manual:
        return sharding
    new = []
    for entry in sharding.spec:
        if entry is None:
            new.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a not in manual)
        new.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return NamedSharding(am, PartitionSpec(*new))


def shard_hint(x, name: str):
    """Apply a sharding constraint if a rule for ``name`` is installed."""
    rules = _RULES.get()
    if rules is None:
        return x
    spec = rules.get(name)
    if spec is None:
        return x
    if _in_manual_region():
        return x
    sh = _rebuild_for_context(spec)
    return jax.lax.with_sharding_constraint(x, _drop_uneven(sh, x.shape))


def ep_context():
    """(mesh, model_axis_name) for expert-parallel shard_map regions, or
    None outside a sharded launch (single-device tests).  Also None inside
    a suspended (already-manual) region: shard_map does not nest, so MoE
    layers traced there must run their local (replicated) path."""
    if _SUSPENDED.get():
        return None
    rules = _RULES.get()
    if rules is None:
        return None
    return rules.get("__ep__")


# -- manual tensor-parallel regions (sharded paged serving) -----------------
#
# The sharded serve path (parallel.plan.PagedServePlan) wraps the paged
# decode/prefill-chunk step in a manual shard_map over the mesh's model
# axis: every projection runs on its local head/d_ff slice and the model
# code marks the point where a Megatron column pair closes with
# ``tp_row_dot`` (the K-contracted matmul) + ``tp_psum``.  Outside a
# manual region (single-device tests, GSPMD launches) the marks are
# no-ops, so the model stays pure single-device code.
#
# Two reduction modes, mirroring the paged kernel's exact/online split:
#
#   * ``"gather"`` — all-gather the column-sharded intermediate (a pure
#     concatenation, in shard order == the unsharded column order) and run
#     the closing matmul replicated against the FULL row weight.  Every
#     activation is then BIT-IDENTICAL to the single-device trace — the
#     mode the byte-identical serve invariant is tested under (and the
#     CPU default).
#   * ``"psum"``   — classic Megatron: row-sharded weight, f32 partial
#     sums, ONE psum per block, round to the activation dtype after.
#     Minimal collective bytes and no replicated matmul — the production
#     accelerator mode; equal to single-device up to f32 reassociation of
#     the K split (token streams agree in practice, not by construction).

_TP_AXIS: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "manual_tp_axis", default=None)
_TP_MODE: contextvars.ContextVar[str] = contextvars.ContextVar(
    "manual_tp_mode", default="gather")


@contextlib.contextmanager
def manual_tp_axis(axis: str, mode: str = "gather"):
    """Declare that the enclosed trace runs inside a manual shard_map over
    ``axis``, closing each column/row pair per ``mode`` (see above)."""
    if mode not in ("gather", "psum"):
        raise ValueError(f"mode={mode!r} (want 'gather' or 'psum')")
    token = _TP_AXIS.set(axis)
    mtoken = _TP_MODE.set(mode)
    try:
        yield
    finally:
        _TP_MODE.reset(mtoken)
        _TP_AXIS.reset(token)


@contextlib.contextmanager
def no_manual_tp():
    """Disable the TP marks for the enclosed trace: subtrees whose weights
    run REPLICATED inside a manual region (MoE experts, shared experts)
    must close no pair — their matmuls are already complete."""
    token = _TP_AXIS.set(None)
    try:
        yield
    finally:
        _TP_AXIS.reset(token)


def tp_psum(x):
    """Close a Megatron column->row pair: the one reduction per block in
    ``"psum"`` mode; identity in ``"gather"`` mode (the all-gather inside
    ``tp_row_dot`` already completed the value) and outside manual TP."""
    axis = _TP_AXIS.get()
    if axis is None or _TP_MODE.get() == "gather":
        return x
    return jax.lax.psum(x, axis)


def tp_row_dot(x, w):
    """The K-contracted matmul closing a Megatron pair.

    Outside a manual region this is exactly ``x @ w``.  In ``"gather"``
    mode, ``x``'s sharded last dim is all-gathered (tiled, shard order ==
    column order) and the matmul runs against the full replicated ``w`` —
    bit-identical to the single-device dot.  In ``"psum"`` mode ``w`` is
    row-sharded and the contraction runs with f32 inputs so each shard's
    PARTIAL sum stays unrounded until ``tp_psum``: XLA accumulates a bf16
    dot in f32 and rounds once at the end, so rounding partials to bf16
    before the reduction would land a bf16 quantum off — the caller casts
    back to the activation dtype AFTER the psum instead.

    Packed (quantized) row weights route through ``quant.linear.qdot`` in
    the unsharded / gather paths; the psum path dequantizes to f32 first
    so the partial-sum contract above is unchanged."""
    from repro.quant.linear import is_packed, qdot
    axis = _TP_AXIS.get()
    if axis is None:
        return qdot(x, w)
    if _TP_MODE.get() == "gather":
        full = jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)
        return qdot(full, w)
    import jax.numpy as jnp
    if is_packed(w):
        from repro.quant import formats
        w = formats.dequantize_any(w, jnp.float32)
    return x.astype(jnp.float32) @ w.astype(jnp.float32)
