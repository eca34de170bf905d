"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls this.

Mesh layout:
  single pod : (data=16, model=16)            — 256 chips (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     — 512 chips

The ``model`` axis carries TP/EP/CP (weights, experts, KV$-context); the
``data`` axis carries DP and the FSDP weight shard; ``pod`` is the slow
(DCN-ish) axis used for DP + gradient-compressed cross-pod reduction.

Every mesh in the repo is built by ``make_mesh``, which makes every axis
``Auto``: sharding hints (``with_sharding_constraint``), ``jnp.repeat``
and partial-manual ``shard_map`` regions all need Auto axes, and
``jax.make_mesh`` defaults to Explicit ones.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """A mesh of ``shape`` named ``axes`` with every axis ``Auto``, over
    the first ``prod(shape)`` visible devices."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_small_mesh(n_devices: int | None = None, model_axis: int | None = None):
    """A (data, model) mesh over whatever devices exist (tests/examples)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    model = model_axis or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def mesh_chip_count(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def parse_mesh(spec: str):
    """``"DxM"`` -> a (data=D, model=M) mesh over the visible devices.

    The serve launcher's ``--mesh 2x4`` etc.; ``D * M`` must equal the
    device count (use ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    for CPU host devices).
    """
    try:
        d, m = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"--mesh wants DxM (e.g. 2x4), got {spec!r}") from None
    n = len(jax.devices())
    if d * m != n:
        raise ValueError(f"mesh {d}x{m} needs {d * m} devices, "
                         f"have {n} (set "
                         f"XLA_FLAGS=--xla_force_host_platform_device_count)")
    return make_mesh((d, m), ("data", "model"))
