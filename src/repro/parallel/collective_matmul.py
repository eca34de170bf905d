"""Ring collective matmuls — the JAX/TPU realization of the RPU's
distributed VMM dataflow (paper §IV).

The paper's scheme: weights are column-sharded across cores; each core
starts computing on its *local* activation fragment immediately while
forwarding fragments around the ring, so the vector broadcast is hidden
behind compute ("This strategy mirrors Cannon's algorithm ... data movement
and computation are interleaved").  The row-sharded variant needs a
reduction "always on the compute-network critical path".

JAX analogues (used inside ``jax.shard_map`` over a tensor-parallel axis):

  * ``ring_allgather_matmul``   — x fragment (B, K/P) x W columns (K, N/P):
    P steps, each overlapping one chunk matmul with one ``ppermute`` hop of
    the activation fragment.  == the paper's broadcast-overlap VMM.
  * ``ring_matmul_reducescatter`` — x fragment (B, K/P) x W rows (K/P, N):
    partial outputs travel the ring accumulating; each device ends with its
    fully-reduced (B, N/P) chunk.  == the paper's reduction-tree path.

Both are numerically identical (up to fp reassociation) to the dense
``x @ w`` and are property-tested against it.  XLA schedules the
``ppermute`` asynchronously (collective-permute-start/done), overlapping
the hop with the chunk matmul — the same decoupled compute/network
pipelining the Reasoning Core implements in hardware.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _axis_size(axis_name) -> int:
    return jax.lax.psum(1, axis_name)


def ring_allgather_matmul(x_frag: jnp.ndarray, w_cols: jnp.ndarray,
                          axis_name: str) -> jnp.ndarray:
    """Column-sharded VMM with broadcast-compute overlap.

    x_frag: (..., B, K/P) local activation fragment (K sharded)
    w_cols: (K, N/P) local full-K column shard
    returns (..., B, N/P) local output columns.
    """
    p = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    kp = x_frag.shape[-1]
    nl = w_cols.shape[-1]
    perm = [(j, (j + 1) % p) for j in range(p)]

    def step(i, carry):
        acc, frag = carry
        src = jax.lax.rem(idx - i + p, p)          # origin of current fragment
        w_slice = jax.lax.dynamic_slice_in_dim(w_cols, src * kp, kp, axis=0)
        acc = acc + jnp.matmul(frag, w_slice.astype(frag.dtype),
                               preferred_element_type=jnp.float32)
        frag = jax.lax.cond(
            i < p - 1,
            lambda f: jax.lax.ppermute(f, axis_name, perm),
            lambda f: f,
            frag)
        return acc, frag

    acc0 = jnp.zeros(x_frag.shape[:-1] + (nl,), jnp.float32)
    acc, _ = jax.lax.fori_loop(0, p, step, (acc0, x_frag), unroll=True)
    return acc.astype(x_frag.dtype)


def ring_matmul_reducescatter(x_frag: jnp.ndarray, w_rows: jnp.ndarray,
                              axis_name: str) -> jnp.ndarray:
    """Row-sharded VMM with ring reduce-scatter overlap.

    x_frag: (..., B, K/P) local activation fragment
    w_rows: (K/P, N) local row shard
    returns (..., B, N/P): device d holds output columns [d*N/P, (d+1)*N/P).
    """
    p = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    n = w_rows.shape[-1]
    nl = n // p
    perm = [(j, (j + 1) % p) for j in range(p)]

    def chunk(c):
        w_slice = jax.lax.dynamic_slice_in_dim(w_rows, c * nl, nl, axis=1)
        return jnp.matmul(x_frag, w_slice.astype(x_frag.dtype),
                          preferred_element_type=jnp.float32)

    # partial for chunk (idx - i - 1) arrives having visited i devices;
    # add our contribution and pass on.  After P-1 hops we hold our own
    # fully-reduced chunk.
    def step(i, acc):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        c = jax.lax.rem(idx - i - 1 + 2 * p, p)
        return acc + chunk(c)

    acc0 = chunk(jax.lax.rem(idx + p - 1, p))      # i = 0 chunk (no recv yet)
    acc = jax.lax.fori_loop(1, p, step, acc0, unroll=True)
    return acc.astype(x_frag.dtype)


# ---------------------------------------------------------------------------
# pjit-level wrappers: apply the ring kernels over a mesh axis via shard_map
# ---------------------------------------------------------------------------


def tp_linear_overlapped(x: jnp.ndarray, w: jnp.ndarray, mesh,
                         tp_axis: str = "model", mode: str = "ag") -> jnp.ndarray:
    """Tensor-parallel linear with RPU-style ring overlap.

    x: (..., K) with its last dim sharded over ``tp_axis``;
    w: (K, N) column-sharded (mode="ag") or row-sharded (mode="rs").
    Output: (..., N) sharded over ``tp_axis`` on the last dim.

    ``shard_map`` is manual only over ``tp_axis`` (``axis_names``); any
    data-parallel sharding of the leading dims stays on the automatic
    (GSPMD) side, so this composes with pjit-sharded batches.
    """
    nb = x.ndim - 1
    lead = (None,) * nb

    if mode == "ag":
        in_specs = (P(*lead, tp_axis), P(None, tp_axis))
        fn = ring_allgather_matmul
    elif mode == "rs":
        in_specs = (P(*lead, tp_axis), P(tp_axis, None))
        fn = ring_matmul_reducescatter
    else:
        raise ValueError(mode)

    return shard_map(
        functools.partial(fn, axis_name=tp_axis),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(*lead, tp_axis),
        axis_names={tp_axis},
        check_vma=False,
    )(x, w)
