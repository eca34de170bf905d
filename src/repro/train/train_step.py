"""Train step factory: loss -> grads -> AdamW, with optional activation
rematerialization and cross-pod int8 gradient compression.

``make_train_step(model, opt_cfg)`` returns the function the dry-run lowers
for ``train_*`` shapes and the launcher jits for real runs.

Compression path (``compress_pods=True``): the step is wrapped in a
``shard_map`` manual ONLY over the ``pod`` axis — intra-pod DP reduction
and tensor parallelism stay on the automatic (GSPMD) side — and the
cross-pod gradient mean uses int8 error-feedback compression
(``parallel.compression``), cutting the slow inter-pod wire bytes ~8x.
The error-feedback residual is part of TrainState (leading pod axis).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.model import Model
from repro.parallel import compression
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    err: Any = None          # cross-pod compression residual (or None)

    def tree_flatten(self):
        return (self.params, self.opt_state, self.err), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def step(self):
        return self.opt_state["step"]


def init_train_state(model: Model, key, *, n_pods: int = 0,
                     state_dtype: str = "float32") -> TrainState:
    params = model.init(key)
    err = None
    if n_pods:
        err = jax.tree.map(
            lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params)
    return TrainState(params=params,
                      opt_state=init_opt_state(params, state_dtype), err=err)


def make_train_step(model: Model, opt_cfg: AdamWConfig, *,
                    remat: bool = False,
                    compress_pods: bool = False,
                    mesh=None,
                    pod_axis: str = "pod"):
    """Build ``train_step(state, batch) -> (state, metrics)``."""

    def loss_fn(params, batch):
        return model.loss(params, batch, remat=remat)

    if not compress_pods:
        def train_step(state: TrainState, batch: dict):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
            new_params, new_opt, metrics = adamw_update(
                opt_cfg, state.params, grads, state.opt_state)
            metrics["loss"] = loss
            return TrainState(new_params, new_opt, state.err), metrics
        return train_step

    assert mesh is not None and pod_axis in mesh.axis_names

    def train_step(state: TrainState, batch: dict):
        def per_pod(params, batch_local, err_local):
            # local (per-pod) grads; data/model axes remain automatic.
            loss, grads = jax.value_and_grad(loss_fn)(params, batch_local)
            err_local = jax.tree.map(lambda e: e[0], err_local)
            synced, new_err = compression.tree_compressed_mean(
                grads, err_local, pod_axis)
            loss = jax.lax.pmean(loss, pod_axis)
            new_err = jax.tree.map(lambda e: e[None], new_err)
            return loss, synced, new_err

        pspec = jax.tree.map(lambda _: P(), state.params)
        bspec = jax.tree.map(lambda _: P(pod_axis), batch)
        espec = jax.tree.map(lambda _: P(pod_axis), state.err)
        loss, grads, new_err = shard_map(
            per_pod, mesh=mesh,
            in_specs=(pspec, bspec, espec),
            out_specs=(P(), pspec, espec),
            axis_names={pod_axis}, check_vma=False,
        )(state.params, batch, state.err)

        new_params, new_opt, metrics = adamw_update(
            opt_cfg, state.params, grads, state.opt_state)
        metrics["loss"] = loss
        return TrainState(new_params, new_opt, new_err), metrics

    return train_step
