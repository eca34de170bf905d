"""ParallelPlan / collective-matmul / gradient-compression tests.

Plan tests build NamedShardings for every assigned arch's full param tree
on the production meshes via abstract mesh devices (no allocation) and
assert even divisibility — exactly the property ``jit in_shardings``
enforces in the dry-run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import AbstractMesh, Mesh, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config, reduced_config
from repro.launch import shapes as shp
from repro.launch.mesh import make_mesh
from repro.models.model import build_model
from repro.parallel import compression
from repro.parallel.plan import make_plan
from repro.train.optimizer import init_opt_state


def _fake_mesh(shape, axes):
    """AbstractMesh-backed mesh: lets us build NamedShardings for a 512-chip
    topology inside the single-device test process."""
    return AbstractMesh(shape, axes)


def _check_divisible(shardings, tree):
    def chk(path, sh, leaf):
        spec = sh.spec
        for dim in range(leaf.ndim):
            entry = spec[dim] if dim < len(spec) else None
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            prod = 1
            for a in axes:
                prod *= sh.mesh.shape[a]
            assert leaf.shape[dim] % prod == 0, (path, leaf.shape, spec)
    jax.tree_util.tree_map_with_path(
        lambda p, s, l: chk(p, s, l), shardings, tree)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("mesh_shape,axes", [
    ((16, 16), ("data", "model")),
    ((2, 16, 16), ("pod", "data", "model")),
])
@pytest.mark.parametrize("shape_name", list(shp.SHAPES))
def test_plan_divisibility_all_cells(arch, mesh_shape, axes, shape_name):
    cfg = get_config(arch)
    shape = shp.SHAPES[shape_name]
    ok, _ = shp.cell_supported(cfg, shape)
    if not ok:
        pytest.skip("cell not runnable")
    mesh = _fake_mesh(mesh_shape, axes)
    plan = make_plan(cfg, mesh, global_batch=shape.global_batch,
                     shape_kind=shape.kind)
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    _check_divisible(plan.param_shardings(params), params)
    if shape.kind == "train":
        opt = jax.eval_shape(lambda: init_opt_state(params))
        _check_divisible(plan.param_shardings(opt), opt)
    if shape.kind in ("decode", "long_decode"):
        cache = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        _check_divisible(plan.cache_shardings(cache), cache)


def test_plan_kinds():
    mesh = _fake_mesh((16, 16), ("data", "model"))
    cfg = get_config("qwen3-14b")
    tr = make_plan(cfg, mesh, global_batch=256, shape_kind="train")
    assert tr.fsdp == ("data",) and tr.seq_parallel and not tr.ep
    ld = make_plan(cfg, mesh, global_batch=1, shape_kind="long_decode")
    assert ld.dp == () and ld.cache_seq == ("data", "model")
    # dense decode with divisible widths: full-TP (the paper's regime —
    # one weight stream for the whole batch)
    de = make_plan(cfg, mesh, global_batch=128, shape_kind="decode")
    assert de.dp == () and de.tp == ("data", "model")
    # MoE decode keeps the DP plan (128 experts don't span 256 shards)
    big = make_plan(get_config("llama4-maverick-400b-a17b"), mesh,
                    global_batch=128, shape_kind="decode")
    assert big.dp == ("data",) and big.fsdp == ("data",)
    # SWA dims (kv 640) don't divide 256: DP plan
    sw = make_plan(get_config("h2o-danube-1.8b"), mesh, global_batch=128,
                   shape_kind="decode")
    assert sw.dp == ("data",) and sw.cache_seq == "model"


# ---------------------------------------------------------------------------
# Ring collective matmul (the paper's broadcast-overlap VMM, §IV)
# ---------------------------------------------------------------------------


def _ring_devices():
    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs >= 2 devices for a ring; covered by dry-run")
    return n


def test_ring_allgather_matmul_matches_dense():
    from repro.parallel.collective_matmul import ring_allgather_matmul
    n = _ring_devices()
    mesh = make_mesh((n,), ("model",))
    k, m, nn = 8 * n, 16, 32
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, nn), jnp.float32)

    def f(x_frag, w_cols):
        return ring_allgather_matmul(x_frag, w_cols, axis_name="model")

    out = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(None, "model"), P(None, "model")),
        out_specs=P(None, "model")))(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)


def test_ring_matmul_reducescatter_matches_dense():
    from repro.parallel.collective_matmul import ring_matmul_reducescatter
    n = _ring_devices()
    mesh = make_mesh((n,), ("model",))
    k, m, nn = 8 * n, 16, 8 * n
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, nn), jnp.float32)

    def f(x_frag, w_rows):
        return ring_matmul_reducescatter(x_frag, w_rows, axis_name="model")

    out = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
        out_specs=P(None, "model")))(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Gradient compression (cross-pod DP)
# ---------------------------------------------------------------------------


def test_int8_roundtrip_error_feedback():
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (256,), jnp.float32)
    q, scale = compression.int8_quantize(g)
    gd = compression.int8_dequantize(q, scale)
    assert float(jnp.max(jnp.abs(gd - g))) <= float(scale) + 1e-7


def test_error_feedback_accumulates_to_true_mean():
    """With error feedback, repeated compressed means converge: the running
    residual keeps what quantization dropped."""
    g = jnp.asarray([1e-4] * 64, jnp.float32)  # tiny values vanish in int8
    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    for _ in range(200):
        q, scale = compression.int8_quantize(g + err)
        sent = compression.int8_dequantize(q, scale)
        err = g + err - sent
        total = total + sent
    mean_sent = total / 200.0
    np.testing.assert_allclose(np.asarray(mean_sent), np.asarray(g),
                               rtol=0.05, atol=1e-6)


# ---------------------------------------------------------------------------
# PagedServePlan (tensor-parallel paged serving)
# ---------------------------------------------------------------------------


def test_paged_serve_plan_specs_and_local_config():
    from repro.parallel.plan import make_paged_serve_plan, paged_kv_token_bytes
    import dataclasses
    cfg = dataclasses.replace(reduced_config(get_config("qwen3-14b")),
                              n_heads=8, n_kv_heads=4)
    model = build_model(cfg)
    mesh = _fake_mesh((2, 4), ("data", "model"))
    plan = make_paged_serve_plan(cfg, mesh, reduce="gather")
    lc = plan.local_config(cfg)
    assert (lc.n_heads, lc.n_kv_heads, lc.d_ff) == (2, 1, cfg.d_ff // 4)
    # pool specs shard the KV-head lanes of the (reps-stacked) gqa pools
    # (L, P, page, KVH * HD)
    specs = plan.pool_specs(model)
    leaf = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))[0]
    assert leaf == P(None, None, None, "model")
    # gather mode: column weights shard, row weights stay replicated
    params = model.init(jax.random.PRNGKey(0))
    pspecs = plan.param_specs(params)
    stack = pspecs["stacks"][0][0]
    assert stack["attn"]["wq"] == P(None, None, "model")
    assert stack["attn"]["wo"] == P()
    assert stack["mlp"]["w_gate"] == P(None, None, "model")
    assert pspecs["embed"] == P()
    # psum mode row-shards the closing weight instead
    psplan = make_paged_serve_plan(cfg, mesh, reduce="psum")
    pstack = psplan.param_specs(params)["stacks"][0][0]
    assert pstack["attn"]["wo"] == P(None, "model", None)
    # per-device KV bytes/token shrink 1/TP
    assert (paged_kv_token_bytes(model, tp=4)
            == paged_kv_token_bytes(model, tp=1) // 4)
    assert plan.psum_bytes_per_step(model, num_slots=8) > 0


def test_paged_serve_plan_quantized_pool_and_packed_param_specs():
    """fp8 pools add k_scale/v_scale leaves — every leaf (codes AND
    scales) shards the KV-head axis — and mxfp4-packed params get the
    parent weight's partition spec on both pytree children, so the TP
    serve path shards the packed codes/scales like the dense weight."""
    from repro.parallel.plan import make_paged_serve_plan, \
        paged_kv_token_bytes
    from repro.quant.formats import PackedMXFP4
    from repro.quant.linear import quantize_params
    import dataclasses
    cfg = dataclasses.replace(reduced_config(get_config("qwen3-14b")),
                              n_heads=8, n_kv_heads=4)
    model = build_model(cfg)
    mesh = _fake_mesh((2, 4), ("data", "model"))
    plan = make_paged_serve_plan(cfg, mesh, reduce="gather")
    specs = plan.pool_specs(model, cache_dtype="fp8")
    leaves = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    dense = jax.tree.leaves(plan.pool_specs(model),
                            is_leaf=lambda s: isinstance(s, P))
    assert len(leaves) == 2 * len(dense)      # + k_scale/v_scale per pool
    # codes (L, P, page, KVH * HD) and scales (L, P, page, KVH) both shard
    # their last, KV-head axis
    assert set(leaves) == {P(None, None, None, "model")}
    # packed param children inherit the parent leaf's spec
    params = model.init(jax.random.PRNGKey(0))
    qp = quantize_params(params, "mxfp4")
    pspecs = plan.param_specs(qp)
    wq = pspecs["stacks"][0][0]["attn"]["wq"]
    assert isinstance(qp["stacks"][0][0]["attn"]["wq"], PackedMXFP4)
    assert wq.codes == wq.scales == P(None, None, "model")
    assert pspecs["stacks"][0][0]["attn"]["wo"].codes == P()  # gather mode
    # sharded packed bytes divide evenly: N is the sharded axis for both
    # children and the mesh TP degree divides it
    for leaf in (qp["stacks"][0][0]["attn"]["wq"].codes,
                 qp["stacks"][0][0]["attn"]["wq"].scales):
        assert leaf.shape[-1] % 4 == 0
    # quantized per-token pool bytes still scale 1/TP on the code leaves
    assert paged_kv_token_bytes(model, tp=4, cache_dtype="fp8") \
        == paged_kv_token_bytes(model, tp=1, cache_dtype="fp8") // 4


def test_paged_serve_plan_kv_head_replication():
    """llama3-style kvh < TP: the plan replicates each KV head on tp/kvh
    shards instead of raising — local model runs 1 KV head/shard, the
    pools widen to tp heads, and capacity accounting counts replicas."""
    from repro.parallel.plan import make_paged_serve_plan, \
        paged_kv_token_bytes
    import dataclasses
    cfg = dataclasses.replace(reduced_config(get_config("qwen3-14b")),
                              n_heads=8, n_kv_heads=2)
    model = build_model(cfg)
    mesh = _fake_mesh((1, 8), ("data", "model"))
    plan = make_paged_serve_plan(cfg, mesh, reduce="gather")
    assert plan.kv_repl == 4
    lc = plan.local_config(cfg)
    assert (lc.n_heads, lc.n_kv_heads) == (1, 1)
    pc = plan.pool_config(cfg)
    assert pc.n_kv_heads == 8                    # widened to tp heads
    # wk/wv columns repeat per head group; wq untouched
    params = model.init(jax.random.PRNGKey(0))
    prep = plan.prepare_params(params, cfg)
    wk = params["stacks"][0][0]["attn"]["wk"]
    wkp = prep["stacks"][0][0]["attn"]["wk"]
    assert wkp.shape[-1] == wk.shape[-1] * 4
    hd = cfg.hd
    w = np.asarray(wk).reshape(*wk.shape[:-1], 2, hd)
    wp = np.asarray(wkp).reshape(*wk.shape[:-1], 8, hd)
    for g in range(8):
        np.testing.assert_array_equal(wp[..., g, :], w[..., g // 4, :])
    np.testing.assert_array_equal(np.asarray(prep["stacks"][0][0]["attn"]
                                             ["wq"]),
                                  np.asarray(params["stacks"][0][0]["attn"]
                                             ["wq"]))
    # per-device KV bytes bottom out at ONE head (kvh/tp * kv_repl)
    full = paged_kv_token_bytes(model, tp=1)
    assert paged_kv_token_bytes(model, tp=8, kv_repl=4) == full // 2
    # still an error when kvh neither divides nor is divided by tp
    bad = dataclasses.replace(cfg, n_kv_heads=3)
    with pytest.raises(ValueError, match="n_kv_heads"):
        make_paged_serve_plan(bad, mesh)


def test_paged_serve_plan_mla_pools_replicated():
    from repro.parallel.plan import make_paged_serve_plan
    cfg = reduced_config(get_config("deepseek-v2-lite-16b"))
    model = build_model(cfg)
    mesh = _fake_mesh((2, 4), ("data", "model"))
    plan = make_paged_serve_plan(cfg, mesh)
    for spec in jax.tree.leaves(plan.pool_specs(model),
                                is_leaf=lambda s: isinstance(s, P)):
        assert spec == P()                 # latent pools shard nothing
    params = model.init(jax.random.PRNGKey(0))
    pspecs = plan.param_specs(params)
    moe_stack = pspecs["stacks"][-1][0]
    assert moe_stack["attn"]["w_uk"][-1] == "model"    # heads column-shard
    # MoE experts replicate inside the manual region (no nested EP)
    assert all(s == P() for s in jax.tree.leaves(
        moe_stack["moe"], is_leaf=lambda s: isinstance(s, P)))


def test_paged_serve_plan_validation():
    from repro.parallel.plan import make_paged_serve_plan
    import dataclasses
    mesh = _fake_mesh((2, 4), ("data", "model"))
    cfg = reduced_config(get_config("qwen3-14b"))
    # kvh=2 on 4-way TP replicates KV heads (no longer an error)
    assert make_paged_serve_plan(cfg, mesh).kv_repl == 2
    # kvh that neither divides nor divides into TP still fails
    bad = dataclasses.replace(cfg, n_heads=12, n_kv_heads=3)
    with pytest.raises(ValueError, match="n_kv_heads"):
        make_paged_serve_plan(bad, mesh)
    with pytest.raises(NotImplementedError, match="SSM"):
        make_paged_serve_plan(reduced_config(get_config("mamba2-370m")), mesh)
    with pytest.raises(ValueError, match="axis"):
        make_paged_serve_plan(cfg, _fake_mesh((8,), ("data",)))
