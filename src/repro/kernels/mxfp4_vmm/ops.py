"""Public op wrapper for the MXFP4 VMM kernel.

``mxfp4_matmul`` is the user-facing op: takes a ``PackedMXFP4`` weight and
(B, K) activations, dispatches to the Pallas kernel (interpret-mode on CPU,
compiled on TPU).  On the CPU, shapes the kernel's tiling can't cover (tiny
smoke configs) fall back to the jnp oracle; the fallback is *surfaced*: it
bumps ``FALLBACK_STATS`` and warns once (the llama3-8b serve projections
are asserted tileable in tests).  On an accelerator such a shape raises, so
no deployment serves through the oracle unnoticed.
"""
from __future__ import annotations

import warnings

import jax.numpy as jnp

from repro.quant.formats import MX_BLOCK, PackedMXFP4
from repro.kernels import on_cpu
from repro.kernels.mxfp4_vmm.kernel import mxfp4_vmm
from repro.kernels.mxfp4_vmm.ref import mxfp4_vmm_ref

# trace-time dispatch counters: {"kernel": .., "fallback": ..}; a fallback
# also warns once per process so silent oracle serving is visible
FALLBACK_STATS = {"kernel": 0, "fallback": 0}
_warned = False


def mxfp4_tileable(k: int, n: int, *, block_n: int = 256,
                   block_k: int = 512) -> bool:
    """True when a (K, N) mxfp4 weight takes the Pallas kernel path."""
    bk, bn = min(block_k, k), min(block_n, n)
    return k % bk == 0 and bk % MX_BLOCK == 0 and n % bn == 0


def _note_fallback(k: int, n: int) -> None:
    global _warned
    FALLBACK_STATS["fallback"] += 1
    if not _warned:
        _warned = True
        warnings.warn(
            f"mxfp4_matmul: weight shape ({k}, {n}) is not tileable by the "
            f"Pallas VMM kernel; using the jnp dequant oracle (reported "
            f"once; see kernels.mxfp4_vmm.ops.FALLBACK_STATS)",
            RuntimeWarning, stacklevel=3)


def mxfp4_matmul(x: jnp.ndarray, w: PackedMXFP4, *,
                 block_n: int = 256, block_k: int = 512,
                 out_dtype=jnp.bfloat16, impl: str = "auto") -> jnp.ndarray:
    """x: (..., K) @ dequant(w): (K, N) -> (..., N).

    ``impl``: "fused" runs the Pallas kernel (interpret-mode on CPU),
    "reference" the jnp oracle, "auto" picks the oracle on CPU (interpret
    mode inside a serve step is orders of magnitude slower) and the kernel
    on accelerators.  Non-tileable shapes take the oracle on the CPU —
    counted in ``FALLBACK_STATS`` and warned once — and raise
    ``ValueError`` on an accelerator.
    """
    if impl not in ("auto", "fused", "reference"):
        raise ValueError(f"impl must be auto|fused|reference, got {impl!r}")
    k, n = w.shape[-2:]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k).astype(jnp.bfloat16)
    if impl == "auto":
        impl = "reference" if on_cpu() else "fused"
    tileable = mxfp4_tileable(k, n, block_n=block_n, block_k=block_k)
    if impl == "fused" and not tileable:
        if not on_cpu():
            raise ValueError(
                f"mxfp4_matmul: weight shape ({k}, {n}) is not tileable by "
                f"the Pallas VMM kernel (block_k={block_k}, "
                f"block_n={block_n})")
        _note_fallback(k, n)
        impl = "reference"
    if impl == "reference":
        out = mxfp4_vmm_ref(x2, w.codes, w.scales)
    else:
        FALLBACK_STATS["kernel"] += 1
        out = mxfp4_vmm(x2, w.codes, w.scales, block_n=min(block_n, n),
                        block_k=min(block_k, k), interpret=on_cpu())
    return out.reshape(*lead, n).astype(out_dtype)
