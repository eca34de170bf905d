"""Compile rehearsals: the serve path's Pallas kernels at published widths,
compiled for a described (not attached) TPU v5e.

Interpret mode on the CPU runs a kernel's arithmetic but none of the TPU
lowering's rules (block tiling, supported casts and shifts, VMEM), so a
kernel can pass every interpret-mode test and still be refused by the
chip's compiler.  These tests compile each kernel with the TPU compiler
that ships with jaxlib and check that the Pallas call survives into the
program as a ``tpu_custom_call``.  Nothing runs: they say nothing about
results or times.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.models  # noqa: F401  (import order: models before kernels.ref)
from repro.kernels.decode_attention.paged_kernel import paged_decode_attention
from repro.kernels.decode_attention.paged_mla_kernel import (
    latent_lanes, paged_mla_decode)
from repro.kernels.mxfp4_vmm.kernel import mxfp4_vmm
from repro.quant.formats import MX_BLOCK

SLOTS, MAX_LEN, PAGE, N_LAYERS = 8, 2048, 16, 32
N_BLOCKS = MAX_LEN // PAGE
N_PAGES = 1 + SLOTS * N_BLOCKS


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # an executable compiled for a described chip cannot be read back here:
    # keep the persistent cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("heads,kv_heads,head_dim", [
    (32, 32, 96),        # phi3-mini-3.8b (MHA)
    (32, 8, 128),        # llama3-8b (GQA 4:1)
    (8, 8, 96),          # phi3-mini-3.8b, a shard of 4 chips
])
def test_paged_decode_online_bf16_compiles(one_chip, heads, kv_heads,
                                           head_dim):
    """Layer-stacked pools, as a scanned decode step passes them."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((N_LAYERS, N_PAGES, PAGE, kv_heads * head_dim), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v, t, p, layer: paged_decode_attention(
            q, k, v, t, p, layer=layer),
        s((SLOTS, heads, head_dim), jnp.bfloat16), pool, pool,
        s((SLOTS, N_BLOCKS), jnp.int32), s((SLOTS,), jnp.int32),
        s((), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_decode_windowed_danube_compiles(one_chip):
    """h2o-danube-1.8b as its cell serves it: GQA 32/8 x 80, a 4096-token
    window over a 1,024-block ring table, 32 slots, 24 stacked layers."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    slots, n_blocks, ring_pages = 32, 1024, 9249
    pool = s((24, ring_pages, PAGE, 8 * 80), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v, t, p, layer: paged_decode_attention(
            q, k, v, t, p, layer=layer, window=4096),
        s((slots, 32, 80), jnp.bfloat16), pool, pool,
        s((slots, n_blocks), jnp.int32), s((slots,), jnp.int32),
        s((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("heads,kv_heads,head_dim,window,cache", [
    (16, 4, 80, 4096, None),     # h2o-danube-1.8b, a shard of 2 chips
    (8, 2, 80, 4096, None),      # h2o-danube-1.8b, a shard of 4 chips
    (25, 5, 64, None, None),     # hymba-1.5b's full-attention layers
    (25, 5, 64, 1024, "fp8"),    # hymba-1.5b's windowed layers, fp8 KV
])
def test_paged_decode_unaligned_widths_compile(one_chip, heads, kv_heads,
                                               head_dim, window, cache):
    """K/V widths that are not whole 128-lane tiles take the page walk."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    slots, n_blocks, n_pages = 32, 1024, 9249
    width = kv_heads * head_dim
    dt = jnp.bfloat16 if cache is None else jnp.float8_e4m3fn
    pool = s((N_LAYERS, n_pages, PAGE, width), dt)
    scales = ([] if cache is None else
              [s((N_LAYERS, n_pages, PAGE, kv_heads), jnp.float32)] * 2)

    def fn(q, k, v, t, p, layer, *sc):
        kw = dict(zip(("k_scales", "v_scales"), sc))
        return paged_decode_attention(q, k, v, t, p, layer=layer,
                                      window=window, **kw)

    text = _compiled_text(
        fn, s((slots, heads, head_dim), jnp.bfloat16), pool, pool,
        s((slots, n_blocks), jnp.int32), s((slots,), jnp.int32),
        s((), jnp.int32), *scales)
    assert "tpu_custom_call" in text


def test_paged_decode_fp8_scales_compiles(one_chip):
    """fp8 codes + per-token f32 scales, dequantized inside the kernel."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((N_PAGES, PAGE, 32 * 96), jnp.float8_e4m3fn)
    scales = s((N_PAGES, PAGE, 32), jnp.float32)
    text = _compiled_text(
        lambda q, k, v, t, p, ks, vs: paged_decode_attention(
            q, k, v, t, p, k_scales=ks, v_scales=vs),
        s((SLOTS, 32, 96), jnp.bfloat16), pool, pool,
        s((SLOTS, N_BLOCKS), jnp.int32), s((SLOTS,), jnp.int32),
        scales, scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("stacked", [False, True])
def test_paged_mla_decode_compiles(one_chip, stacked):
    """DeepSeek-V2-Lite's latent decode: 16 absorbed heads of 512 latent +
    64 rope lanes (640 with the pad) over bf16 latent pages of 16 tokens,
    32 slots of up to 1,024 blocks; one layer's pool (the dense layer) and
    the 26 MoE layers' stacked pool."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    slots, n_blocks, n_pages, width = 32, 1024, 12000, latent_lanes(512, 64)
    pool = s(((26,) if stacked else ()) + (n_pages, PAGE, width),
             jnp.bfloat16)

    def fn(q, lat, t, p, layer):
        return paged_mla_decode(q, lat, t, p, rank=512, scale=0.1147,
                                layer=layer if stacked else None)

    text = _compiled_text(
        fn, s((slots, 16, width), jnp.float32), pool,
        s((slots, n_blocks), jnp.int32), s((slots,), jnp.int32),
        s((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", [(3072, 8192), (8192, 3072)])
def test_mxfp4_vmm_compiles(one_chip, k, n):
    """phi3-mini's MLP up (d_model -> d_ff) and down projections."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda x, c, sc: mxfp4_vmm(x, c, sc),
        s((SLOTS, k), jnp.bfloat16), s((k // 2, n), jnp.uint8),
        s((k // MX_BLOCK, n), jnp.uint8))
    assert "tpu_custom_call" in text
