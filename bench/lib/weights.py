"""Weights made by the benchmark from ``--seed``, in one jitted call on the
device, in the dtype they are served in.

The program hands over only the *shapes* of its parameter tree
(``jax.eval_shape`` of its init); every value is made here, so the plain
reference can make the same values again without taking anything from
the program.  Each leaf is named by its tree path (``stacks/0/0/attn/wq``);
leaves under ``stacks/`` carry a leading layer axis, and layer ``l`` of
such a leaf is drawn from its own key, so the reference can make one
layer at a time.

Values are exact on every platform: 16 random bits per element, read as
a signed integer and scaled by a power of two (a product that rounds
nowhere), then cast to the leaf's dtype.  The distribution is uniform
with about the spread of the usual initialisers: ``1/sqrt(fan_in)`` for
matrices, 0.02 for the embedding, and ``1 +- 0.25`` for norm weights,
which are random here so that a path which ignored them would show.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def path_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def role(name: str) -> str:
    leaf = name.rsplit("/", 1)[-1]
    if leaf.startswith(("ln", "final_norm")) or leaf.endswith("_norm"):
        return "norm"
    if leaf in ("bq", "bk", "bv"):
        return "bias"
    if leaf == "embed":
        return "embed"
    return "matrix"


def _step(std: float) -> float:
    """Power-of-two scale of one integer step for a uniform of ``std``."""
    return 2.0 ** math.floor(math.log2(std * math.sqrt(3.0))) / 32768.0


def leaf_values(key, shape, name: str, dtype):
    """One leaf's (or one layer slice's) values, exactly reproducible."""
    r = role(name)
    if r == "bias":
        return jnp.zeros(shape, dtype)
    bits = jax.random.bits(key, shape, jnp.uint32)
    ints = (bits >> 16).astype(jnp.int32) - 32768          # [-2^15, 2^15)
    x = ints.astype(jnp.float32)
    if r == "norm":
        return (x * (2.0 ** -17) + 1.0).astype(dtype)       # 1 +- 0.25
    std = 0.02 if r == "embed" else 1.0 / math.sqrt(shape[-2])
    return (x * _step(std)).astype(dtype)


def leaf_key(base, name: str):
    """The key of one leaf, from the run's ``base_key``."""
    return jax.random.fold_in(base, zlib.crc32(name.encode()))


def layer_leaf(base, name: str, layer, shape, dtype):
    """Layer ``layer`` of a layer-stacked leaf (``shape`` without L)."""
    return leaf_values(jax.random.fold_in(leaf_key(base, name), layer),
                       tuple(shape), name, dtype)


def make_params(shapes, seed: int):
    """The whole tree ``shapes`` (ShapeDtypeStructs), made on the default
    device in one jitted call (the seed enters as data, so every seed
    runs the same compiled program)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(base):
        out = []
        for path, sds in flat:
            name = path_name(path)
            k = leaf_key(base, name)
            if name.startswith("stacks/"):
                keys = jax.vmap(lambda l: jax.random.fold_in(k, l))(
                    jnp.arange(sds.shape[0]))
                out.append(jax.vmap(lambda kk: leaf_values(
                    kk, sds.shape[1:], name, sds.dtype))(keys))
            else:
                out.append(leaf_values(k, sds.shape, name, sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(base_key(seed))
