"""Quantized execution end to end: mxfp4 weight matmuls and fp8/int8
paged KV pools in the serve path.

The contracts under test:

  * E2M1 rounding is OCP-MX round-to-nearest-even (every midpoint picks
    the even mantissa) and non-finite inputs saturate to +/-6.0;
  * the fused paged decode kernel with fp8/int8 code pools is bit-exact
    against the dequant oracle in ``accum="exact"`` interpret mode (the
    in-loop dequant is the same f32-cast-then-multiply op sequence);
  * greedy serving with ``weight_format="mxfp4"`` (and quantized KV on
    top) emits the SAME tokens as the dense bf16 engine once the weights
    are round-tripped through mxfp4 — quantization is idempotent, so the
    packed engine and the dense engine compute identical matmuls;
  * budget == execution: ``DeploymentSpec.resolve`` reports exactly the
    bytes ``quantize_params`` / ``init_paged_cache`` allocate.
"""
import warnings

import numpy as np
import pytest

import repro.models  # noqa: F401  (import order: models before kernels.ref)
import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced_config
from repro.kernels.decode_attention.paged_kernel import paged_decode_attention
from repro.kernels.decode_attention.ref import paged_decode_attention_ref
from repro.kernels.mxfp4_vmm import ops as vmm_ops
from repro.models.model import build_model
from repro.parallel.plan import paged_kv_token_bytes
from repro.quant import formats
from repro.quant import kv as kvq
from repro.quant.linear import quantizable_leaf, quantize_params, \
    serve_weight_bytes
from repro.runtime.deployment import DeploymentSpec
from repro.runtime.engine import ContinuousServeEngine, ServeEngine
from repro.runtime.scheduler import Request


# ---------------------------------------------------------------------------
# E2M1 rounding (quant-format correctness satellites)
# ---------------------------------------------------------------------------


def _fp4_decode(codes: np.ndarray) -> np.ndarray:
    return formats.FP4_VALUES[codes & 7] * np.where(codes >> 3, -1.0, 1.0)


def test_fp4_rne_midpoints_exhaustive():
    """All 7 E2M1 midpoints, both signs: round-half-to-even mantissa."""
    mids = [0.25, 0.75, 1.25, 1.75, 2.5, 3.5, 5.0]
    want = [0.0, 1.0, 1.0, 2.0, 2.0, 4.0, 4.0]
    x = jnp.asarray(mids + [-m for m in mids], jnp.float32)
    codes = np.asarray(formats._quantize_fp4_codes(x))
    np.testing.assert_array_equal(
        _fp4_decode(codes),
        np.asarray(want + [-w for w in want], np.float32))


def test_fp4_off_midpoints_round_to_nearest():
    rng = np.random.default_rng(0)
    x = rng.uniform(-7.0, 7.0, 512).astype(np.float32)
    mids = (formats.FP4_VALUES[1:] + formats.FP4_VALUES[:-1]) / 2
    for m in mids:                       # ties are tested exhaustively above
        x = np.where(np.isclose(np.abs(x), m), x + 1e-3, x)
    codes = np.asarray(formats._quantize_fp4_codes(jnp.asarray(x)))
    expect_mag = formats.FP4_VALUES[
        np.argmin(np.abs(np.abs(x)[:, None] - formats.FP4_VALUES[None, :]),
                  axis=1)]
    np.testing.assert_array_equal(
        _fp4_decode(codes), np.where(x < 0, -1.0, 1.0) * expect_mag)


def test_fp4_nonfinite_saturates_to_six():
    x = jnp.asarray([np.inf, -np.inf, np.nan], jnp.float32)
    codes = np.asarray(formats._quantize_fp4_codes(x))
    assert np.all(formats.FP4_VALUES[codes & 7] == 6.0)
    assert (codes[0] >> 3) == 0 and (codes[1] >> 3) == 1


def test_mxfp4_tileable_llama3_8b_projections_and_fallback_stats():
    """Every llama3-8b serve projection takes the Pallas kernel path; a
    non-tileable shape falls back to the oracle, counted not silent."""
    for k, n in [(4096, 4096),    # wq / wo
                 (4096, 1024),    # wk / wv (8 KV heads x 128)
                 (4096, 14336),   # w_gate / w_up
                 (14336, 4096)]:  # w_down
        assert vmm_ops.mxfp4_tileable(k, n), (k, n)
    # K=544 is 32-aligned (quantizable) but not 512-tileable
    assert not vmm_ops.mxfp4_tileable(544, 8)
    qw = formats.quantize(
        jax.random.normal(jax.random.PRNGKey(0), (544, 8), jnp.float32),
        "mxfp4")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 544), jnp.bfloat16)
    before = vmm_ops.FALLBACK_STATS["fallback"]
    with warnings.catch_warnings():      # one-shot warning may have fired
        warnings.simplefilter("ignore", RuntimeWarning)
        out = vmm_ops.mxfp4_matmul(x, qw, impl="fused")
    assert vmm_ops.FALLBACK_STATS["fallback"] == before + 1
    ref = vmm_ops.mxfp4_matmul(x, qw, impl="reference")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# fp8/int8 KV quantization + the fused paged decode kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cd", ["fp8", "int8"])
def test_kv_quantize_roundtrip(cd):
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 6, 2, 16),
                          jnp.float32) * 3.0
    codes, scales = kvq.kv_quantize(x, cd)
    assert codes.dtype == kvq.cache_storage_dtype(cd)
    assert scales.dtype == kvq.SCALE_DTYPE and scales.shape == x.shape[:-1]
    xd = np.asarray(kvq.kv_dequantize(codes, scales, jnp.float32))
    tol = 0.07 if cd == "fp8" else 0.01      # e4m3 step vs 1/127
    err = np.max(np.abs(xd - np.asarray(x)), axis=-1)
    amax = np.max(np.abs(np.asarray(x)), axis=-1)
    assert np.all(err <= tol * amax)
    # all-zero vectors quantize to scale 1.0 (finite dequant)
    zc, zs = kvq.kv_quantize(jnp.zeros((2, 3, 8)), cd)
    np.testing.assert_array_equal(np.asarray(zs), 1.0)
    np.testing.assert_array_equal(
        np.asarray(kvq.kv_dequantize(zc, zs)), 0.0)


def _quantized_paged_case(seed, cache, B=3, H=8, KVH=2, D=32, page=8,
                          n_blocks=5):
    """Quantized page pools + permuted page tables + ragged positions."""
    key = jax.random.PRNGKey(seed)
    P = 1 + B * n_blocks
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, P))
    table = jnp.asarray(ids[:B * n_blocks].reshape(B, n_blocks), jnp.int32)
    q = jax.random.normal(key, (B, H, D), jnp.float32)
    kp = jax.random.normal(jax.random.fold_in(key, 1), (P, page, KVH, D))
    vp = jax.random.normal(jax.random.fold_in(key, 2), (P, page, KVH, D))
    pos = jnp.asarray(rng.integers(0, page * n_blocks, B), jnp.int32)
    kc, ks = kvq.kv_quantize(kp, cache)
    vc, vs = kvq.kv_quantize(vp, cache)
    return q, kc, ks, vc, vs, table, pos


def _lanes(pages):
    """(P, page, KVH, D) -> the kernel's serve layout (P, page, KVH * D)."""
    return pages.reshape(pages.shape[:2] + (-1,))


@pytest.mark.parametrize("cd", ["fp8", "int8"])
def test_quantized_paged_kernel_exact_bitwise(cd):
    """Fused in-loop dequant == oracle dequant, bit for bit."""
    q, kc, ks, vc, vs, table, pos = _quantized_paged_case(3, cd)
    ref = paged_decode_attention_ref(q, kc, vc, table, pos,
                                     k_scales=ks, v_scales=vs)
    out = paged_decode_attention(q, _lanes(kc), _lanes(vc), table, pos,
                                 k_scales=ks, v_scales=vs, accum="exact",
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("cd,window", [("fp8", None), ("fp8", 5),
                                       ("int8", None)])
def test_quantized_paged_kernel_online_close(cd, window):
    q, kc, ks, vc, vs, table, pos = _quantized_paged_case(7, cd)
    ref = np.asarray(paged_decode_attention_ref(
        q, kc, vc, table, pos, k_scales=ks, v_scales=vs, window=window),
        np.float32)
    out = np.asarray(paged_decode_attention(
        q, _lanes(kc), _lanes(vc), table, pos, k_scales=ks, v_scales=vs,
        window=window, accum="online", interpret=True), np.float32)
    np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)


def test_quantized_vs_dense_attention_close():
    """A quantized pool approximates the dense pool it was written from."""
    q, kc, ks, vc, vs, table, pos = _quantized_paged_case(11, "fp8")
    kd = kvq.kv_dequantize(kc, ks, jnp.float32)
    vd = kvq.kv_dequantize(vc, vs, jnp.float32)
    dense = np.asarray(paged_decode_attention_ref(q, kd, vd, table, pos),
                       np.float32)
    quant = np.asarray(paged_decode_attention_ref(
        q, kc, vc, table, pos, k_scales=ks, v_scales=vs), np.float32)
    np.testing.assert_array_equal(quant, dense)   # same dequant values


# ---------------------------------------------------------------------------
# End-to-end serving: bf16 == mxfp4 == mxfp4 + quantized KV (greedy)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """Reduced model whose projection weights are round-tripped through
    mxfp4: quantization is then idempotent, so the packed engine computes
    bit-identical matmuls to the dense engine and greedy tokens match
    EXACTLY (the e2e acceptance contract)."""
    cfg = reduced_config(get_config("qwen3-14b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))

    def rt(path, leaf):
        if quantizable_leaf(path, leaf, "mxfp4"):
            p = formats.quantize(leaf, "mxfp4")
            return formats.dequantize(p, "mxfp4").astype(leaf.dtype)
        return leaf

    params = jax.tree_util.tree_map_with_path(rt, params)
    return cfg, model, params


def _greedy(model, params, **kw):
    eng = ContinuousServeEngine(model, params, num_slots=3, page_size=4,
                                num_pages=32, max_len=24, prefill_chunk=5,
                                **kw)
    for i in range(3):
        eng.add_request(Request(rid=i,
                                prompt=np.arange(1 + i, 6 + i,
                                                 dtype=np.int32),
                                max_new_tokens=8))
    while eng.has_unfinished():
        eng.step()
    return eng, [list(r.tokens) for r in eng._requests]


@pytest.fixture(scope="module")
def ref_tokens(served):
    _, model, params = served
    _, toks = _greedy(model, params, cache_dtype=jnp.float32)
    return toks


def test_mxfp4_engine_matches_dense_greedy_exactly(served, ref_tokens):
    _, model, params = served
    eng, toks = _greedy(model, params, cache_dtype=jnp.float32,
                        weight_format="mxfp4")
    assert toks == ref_tokens
    packed = [l for l in jax.tree.leaves(
        eng.params, is_leaf=lambda x: isinstance(x, formats.PackedMXFP4))
        if isinstance(l, formats.PackedMXFP4)]
    assert len(packed) == 7          # wq wk wv wo w_gate w_up w_down


@pytest.mark.parametrize("cd", ["fp8", "int8"])
def test_quantized_kv_engine_matches_dense_greedy(served, ref_tokens, cd):
    """mxfp4 weights + quantized paged KV: same greedy stream on short
    sequences (seeded so near-ties in the logits don't flip argmax)."""
    _, model, params = served
    eng, toks = _greedy(model, params, cache_dtype=cd,
                        weight_format="mxfp4")
    assert toks == ref_tokens
    assert eng.kv_token_bytes_per_device() \
        == paged_kv_token_bytes(model, cache_dtype=cd) \
        < paged_kv_token_bytes(model, cache_dtype=jnp.float32)


def test_static_engine_rejects_quantized_cache(served):
    _, model, params = served
    with pytest.raises(NotImplementedError, match="cache_dtype"):
        ServeEngine(model, params, max_len=24, cache_dtype="fp8")


def test_mla_quantized_pool_not_implemented():
    cfg = reduced_config(get_config("deepseek-v2-lite-16b"))
    model = build_model(cfg)
    with pytest.raises(NotImplementedError, match="MLA"):
        model.init_paged_cache(2, 1, dtype="fp8")


def test_unknown_cache_dtype_rejected(served):
    _, model, params = served
    with pytest.raises(ValueError, match="cache_dtype"):
        ContinuousServeEngine(model, params, num_slots=2, page_size=4,
                              num_pages=8, max_len=16, cache_dtype="fp4")


# ---------------------------------------------------------------------------
# Budget == execution
# ---------------------------------------------------------------------------


def test_resolved_weight_bytes_equal_allocated_bytes(served):
    """``resolve`` prices weights at the EXACT bytes ``quantize_params``
    allocates — packed codes+scales for quantizable leaves, native bytes
    for the rest."""
    _, model, params = served
    spec = DeploymentSpec(sku="rpu-cu", hbmco="hbmco-768MB",
                          weight_format="mxfp4", cache_dtype="fp8",
                          max_len=24, page_size=4, max_slots=3)
    dep = spec.resolve(model, params=params)
    qp = quantize_params(params, "mxfp4")
    allocated = sum(int(np.asarray(l).nbytes) for l in jax.tree.leaves(qp))
    assert dep.weight_bytes_per_device == allocated \
        == serve_weight_bytes(params, "mxfp4")


@pytest.mark.parametrize("cd", ["fp8", "int8", jnp.float32])
def test_paged_kv_token_bytes_match_pool_allocation(served, cd):
    """The accounting helper reports exactly what a pool of that dtype
    allocates, scale metadata included."""
    _, model, _ = served
    per_tok = paged_kv_token_bytes(model, cache_dtype=cd)
    num_pages, page_size = 3, 2
    pools = model.init_paged_cache(num_pages, page_size, dtype=cd)
    total = sum(int(np.asarray(l).nbytes) for l in jax.tree.leaves(pools))
    assert total == per_tok * num_pages * page_size
    if isinstance(cd, str):
        # codes shrink 4x vs f32; the f32 scale leaves are the remainder
        dense = paged_kv_token_bytes(model, cache_dtype=jnp.float32)
        assert dense // 4 < per_tok < dense // 2
