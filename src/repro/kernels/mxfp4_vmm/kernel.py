"""MXFP4 weight-streaming VMM Pallas kernel — the TPU realization of the
RPU's Stream Decoder + TMAC stripe dataflow (paper §V, Fig 7).

Mapping of the paper's microarchitecture onto TPU/Pallas:

  paper                         | this kernel
  ------------------------------+------------------------------------------
  weights compressed in HBM     | codes (uint8 nibbles) + E8M0 scales in HBM
  memory DMA -> memory buffer   | Pallas grid pipeline HBM->VMEM (BlockSpec)
  Stream Decoder (fp4 -> bf16)  | branch-free arithmetic E2M1 decode in VMEM
  TMAC 8x8 weight-streaming     | MXU dot over (bk x bn) dequantized tile
  stripe-based execution        | grid = (N/bn outer, K/bk inner): for one
                                | output stripe, iterate K-tiles (output-
                                | stationary), then advance to next stripe
  output-stationary reg file    | out block revisited across the K grid dim
  decoupled mem/compute pipes   | Pallas double-buffers the next tile's DMA
                                | while the MXU works on the current tile

The kernel computes ``out[B, N] = x[B, K] @ dequant(codes, scales)[K, N]``
with fp32 accumulation.  K must be a multiple of the MX block (32) and of
``block_k``; layouts follow ``repro.quant.formats.PackedMXFP4``:
codes ``(K//2, N)`` (two K-nibbles per byte), scales ``(K//32, N)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant.formats import MX_BLOCK

_E8M0_BIAS = 127.0


def _decode_e2m1(codes: jnp.ndarray) -> jnp.ndarray:
    """Branch-free E2M1 decode: int32 code (0..15) -> f32 value.

    value = sign * (e == 0 ? 0.5*m : (1 + 0.5*m) * 2^(e-1))
    """
    c = codes
    sign = 1.0 - 2.0 * ((c >> 3) & 1).astype(jnp.float32)
    e = ((c >> 1) & 3).astype(jnp.float32)
    m = (c & 1).astype(jnp.float32)
    sub = 0.5 * m
    norm = (1.0 + 0.5 * m) * jnp.exp2(e - 1.0)
    return sign * jnp.where(e == 0.0, sub, norm)


def _vmm_kernel(x_ref, codes_ref, scales_ref, out_ref, *, block_k: int,
                n_k_steps: int):
    """One (stripe j, K-tile k) grid step."""
    k_step = pl.program_id(1)

    # ---- Stream Decoder: dequantize the (block_k, bn) weight tile in VMEM
    # bytes widen to int32 first: the TPU has no 8-bit shifts or casts
    packed = codes_ref[...].astype(jnp.int32)        # (bk//2, bn)
    lo = _decode_e2m1(packed & 0xF)                  # even k
    hi = _decode_e2m1(packed >> 4)                   # odd k
    vals = jnp.stack([lo, hi], axis=1)               # (bk//2, 2, bn)
    vals = vals.reshape(block_k, -1)                 # (bk, bn) interleaved

    exp = (scales_ref[...].astype(jnp.int32).astype(jnp.float32)
           - _E8M0_BIAS)                                # (bk//32, bn)
    scale = jnp.repeat(jnp.exp2(exp), MX_BLOCK, axis=0)      # (bk, bn)
    w_tile = (vals * scale).astype(jnp.bfloat16)

    # ---- TMAC: MXU matmul with fp32 accumulation, output-stationary
    acc = jnp.dot(x_ref[...], w_tile, preferred_element_type=jnp.float32)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(k_step > 0)
    def _accum():
        out_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def mxfp4_vmm(
    x: jnp.ndarray,        # (B, K) bf16 activations
    codes: jnp.ndarray,    # (K//2, N) uint8
    scales: jnp.ndarray,   # (K//32, N) uint8 (E8M0, bias 127)
    *,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Stream-decoded VMM: returns (B, N) f32."""
    b, k = x.shape
    n = codes.shape[1]
    assert codes.shape[0] == k // 2 and scales.shape[0] == k // MX_BLOCK
    block_k = min(block_k, k)
    block_n = min(block_n, n)
    assert k % block_k == 0 and block_k % MX_BLOCK == 0 and block_k % 2 == 0
    assert n % block_n == 0
    n_k_steps = k // block_k

    grid = (n // block_n, n_k_steps)
    return pl.pallas_call(
        functools.partial(_vmm_kernel, block_k=block_k, n_k_steps=n_k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, block_k), lambda j, kk: (0, kk)),
            pl.BlockSpec((block_k // 2, block_n), lambda j, kk: (kk, j)),
            pl.BlockSpec((block_k // MX_BLOCK, block_n), lambda j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
    )(x, codes, scales)
