"""Distributed correctness on a REAL multi-device mesh (8 CPU host
devices, spawned in subprocesses so the main test process keeps its
single device): the sharded train step and decode must match the
single-device results bit-for-bit (same math, different partitioning).
"""
import os
import subprocess
import sys
import textwrap

import pytest

# Every test here compiles a model in an 8-device subprocess (minutes of
# wall time) — heavy tier only.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


def _run(code: str, timeout=1200):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True,
                       timeout=timeout, cwd=REPO)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    return r.stdout


@pytest.mark.parametrize("arch", ["qwen3-14b", "deepseek-v2-lite-16b",
                                  "mamba2-370m"])
def test_sharded_train_step_matches_single_device(arch):
    """One train step on a (2 data x 4 model) mesh with the production
    ParallelPlan (TP + FSDP + seq-parallel + EP/SSM sharding) == the same
    step on one device."""
    out = _run(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced_config
        from repro.models.model import build_model
        from repro.parallel.hints import sharding_rules
        from repro.parallel.plan import ParallelPlan, make_plan
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import init_train_state, make_train_step

        cfg = reduced_config(get_config({arch!r}))
        model = build_model(cfg)
        key = jax.random.PRNGKey(0)
        state = init_train_state(model, key)
        batch = {{"tokens": jax.random.randint(key, (8, 32), 0,
                                               cfg.vocab_size)}}
        step = make_train_step(model, AdamWConfig(lr=1e-3))

        # single device
        s1, m1 = jax.jit(step)(state, batch)
        l1 = float(m1["loss"])

        # 2x4 mesh with the production plan
        mesh = make_mesh((2, 4), ("data", "model"))
        plan = make_plan(cfg, mesh, global_batch=8, shape_kind="train")
        state2 = init_train_state(model, key)
        with mesh, sharding_rules(plan.rules()):
            sh_state = type(state2)(
                params=plan.param_shardings(state2.params),
                opt_state=plan.param_shardings(state2.opt_state), err=None)
            s2, m2 = jax.jit(step, in_shardings=(sh_state,
                             plan.batch_shardings(batch)))(state2, batch)
        l2 = float(m2["loss"])
        assert abs(l1 - l2) < 5e-3, (l1, l2)
        # parameters after the update agree
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=2e-2, rtol=2e-2)
        print("ok", l1, l2)
    """)
    assert "ok" in out


def test_sharded_decode_matches_single_device():
    """Greedy decode on the sharded mesh (TP + context-sharded KV$) ==
    single-device decode, token for token."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced_config
        from repro.models.model import build_model
        from repro.parallel.hints import sharding_rules
        from repro.parallel.plan import make_plan
        from repro.runtime.engine import ServeEngine

        cfg = reduced_config(get_config("qwen3-14b"))
        model = build_model(cfg)
        key = jax.random.PRNGKey(0)
        params = model.init(key)
        toks = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)

        eng = ServeEngine(model, params, max_len=32, donate_cache=False)
        ref = eng.generate({"tokens": toks}, max_new_tokens=8).tokens

        mesh = make_mesh((2, 4), ("data", "model"))
        plan = make_plan(cfg, mesh, global_batch=8, shape_kind="decode")
        with mesh, sharding_rules(plan.rules()):
            eng2 = ServeEngine(model, params, max_len=32,
                               donate_cache=False)
            got = eng2.generate({"tokens": toks}, max_new_tokens=8).tokens
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        print("ok", np.asarray(got)[0].tolist())
    """)
    assert "ok" in out


def test_sharded_paged_continuous_decode_matches_single_device():
    """Tensor-parallel continuous batching on a (2 data x 4 model) mesh:
    KV page pools sharded per KV head, params Megatron column-sharded,
    the fused paged decode step inside one manual shard_map — byte-
    identical to the single-device engine for a greedy/sampled mix,
    through forced preemption-restarts AND prefix-cache hits, with no
    extra compiles per mesh shape and per-device KV bytes/token at 1/TP."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced_config
        from repro.models.model import build_model
        from repro.runtime.engine import ContinuousServeEngine
        from repro.runtime.sampling import SamplingParams
        from repro.runtime.scheduler import Request

        cfg = dataclasses.replace(reduced_config(get_config("qwen3-14b")),
                                  n_heads=8, n_kv_heads=4)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        base = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                             (2, 12), 0, cfg.vocab_size))
        prompts = base[np.array([0, 1, 0, 1, 0, 0])]   # 2 distinct -> hits
        SP = [SamplingParams() if i % 2 == 0 else
              SamplingParams(temperature=0.9, top_k=8, top_p=0.95,
                             seed=100 + i) for i in range(6)]
        mk = lambda: [Request(rid=i, prompt=prompts[i], max_new_tokens=8,
                              sampling=SP[i], arrival_time=0.02 * i)
                      for i in range(6)]

        def engine(mesh=None, num_pages=64, tp_reduce="auto"):
            return ContinuousServeEngine(
                model, params, num_slots=3, page_size=4,
                num_pages=num_pages, max_len=21, prefill_chunk=5, mesh=mesh,
                tp_reduce=tp_reduce)

        ref = engine().run(mk())
        mesh = make_mesh((2, 4), ("data", "model"))
        # roomy pool (prefix hits) + tight pool (forced preemptions)
        seng = engine(mesh)
        got = seng.run(mk())
        tight = engine(mesh, num_pages=12)
        tgot = tight.run(mk())
        tref = engine(num_pages=12).run(mk())
        assert got.prefix_hit_tokens > 0, "no prefix sharing exercised"
        assert tgot.preemptions > 0, "no preemption pressure"
        for i in range(6):
            np.testing.assert_array_equal(ref.results[i], got.results[i])
            np.testing.assert_array_equal(tref.results[i], tgot.results[i])
        # one compiled decode step for the whole greedy/sampled mix
        assert seng._step_fn._cache_size() == 1, \\
            seng._step_fn._cache_size()
        # pools physically shard the KV-head lanes 4-way
        leaf = jax.tree.leaves(seng._pools)[0]
        assert (leaf.addressable_shards[0].data.shape[-1]
                == leaf.shape[-1] // 4), leaf.sharding
        assert (seng.kv_token_bytes_per_device() * 4
                == engine().kv_token_bytes_per_device())
        # psum production mode: execution coverage (row-sharded weights,
        # one f32 psum per block).  Tokens match single-device only up to
        # f32 reassociation — at this toy scale streams can diverge, so
        # assert the run itself: every request completes its full budget
        # through one compiled step, on the same sharded pools.
        peng = engine(mesh, tp_reduce="psum")
        pgot = peng.run(mk())
        assert all(pgot.results[i].shape == (8,) for i in range(6))
        assert all(o.finish_reason == "length"
                   for o in pgot.outputs.values())
        assert peng._step_fn._cache_size() == 1
        print("ok", ref.results[5].tolist())
    """)
    assert "ok" in out


def test_kv_head_replicated_paged_decode_matches_single_device():
    """KV-head replication (n_kv_heads < TP): a 2-KV-head model served on
    a 4-way model axis — each shard holds 2 q heads and ONE replicated KV
    head — stays byte-identical to the single-device engine, and the
    per-device KV bytes/token bottom out at one head (full/kvh) instead
    of shrinking 1/TP."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced_config
        from repro.models.model import build_model
        from repro.runtime.engine import ContinuousServeEngine
        from repro.runtime.sampling import SamplingParams
        from repro.runtime.scheduler import Request

        cfg = dataclasses.replace(reduced_config(get_config("qwen3-14b")),
                                  n_heads=8, n_kv_heads=2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                                (4, 12), 0, cfg.vocab_size))
        SP = [SamplingParams() if i % 2 == 0 else
              SamplingParams(temperature=0.9, top_k=8, top_p=0.95,
                             seed=100 + i) for i in range(4)]
        mk = lambda: [Request(rid=i, prompt=prompts[i], max_new_tokens=8,
                              sampling=SP[i]) for i in range(4)]

        def engine(mesh=None):
            return ContinuousServeEngine(
                model, params, num_slots=3, page_size=4, num_pages=64,
                max_len=21, prefill_chunk=5, mesh=mesh)

        ref = engine().run(mk())
        mesh = make_mesh((2, 4), ("data", "model"))
        seng = engine(mesh)
        assert seng.serve_plan.kv_repl == 2, seng.serve_plan
        got = seng.run(mk())
        for i in range(4):
            np.testing.assert_array_equal(ref.results[i], got.results[i])
        assert seng._step_fn._cache_size() == 1
        # pools widened to 4 KV heads, sharded 4-way -> 1 head per shard
        # (the pool's last axis holds KV heads x head_dim side by side)
        leaf = jax.tree.leaves(seng._pools)[0]
        assert leaf.shape[-1] == 4 * cfg.hd, leaf.shape
        assert leaf.addressable_shards[0].data.shape[-1] == cfg.hd, \
            leaf.sharding
        # accounting: per-device bytes = full / kvh (one head), NOT full/tp
        full = engine().kv_token_bytes_per_device()
        assert seng.kv_token_bytes_per_device() == full // 2
        print("ok", ref.results[1].tolist())
    """)
    assert "ok" in out


def test_sharded_speculative_continuous_matches_single_device():
    """Scheduler-integrated speculation on a (2 data x 4 model) mesh with
    a SEPARATE draft model: the draft gets its own plan and its page
    pools shard per KV head over the model axis (same page-id space as
    the target's), and both greedy and sampled streams stay byte-
    identical to the single-device speculative engine — greedy also to
    the non-speculative engine — with one compiled draft scan and one
    compiled verify step."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced_config
        from repro.models.model import build_model
        from repro.runtime.engine import ContinuousServeEngine
        from repro.runtime.sampling import SamplingParams
        from repro.runtime.scheduler import Request
        from repro.runtime.speculative import SpeculativeConfig

        cfg = dataclasses.replace(reduced_config(get_config("qwen3-14b")),
                                  n_heads=8, n_kv_heads=4)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        dcfg = dataclasses.replace(cfg, name=cfg.name + "-draft",
                                   n_layers=1)
        dm = build_model(dcfg)
        dp = dm.init(jax.random.PRNGKey(3))
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                             (3, 12), 0, cfg.vocab_size))
        SP = [SamplingParams(),
              SamplingParams(temperature=0.9, top_k=8, seed=7),
              SamplingParams()]
        mk = lambda: [Request(rid=i, prompt=toks[i], max_new_tokens=8,
                              sampling=SP[i]) for i in range(3)]
        sc = SpeculativeConfig(draft_model=dm, draft_params=dp, gamma=3)

        def engine(mesh=None, spec=None):
            return ContinuousServeEngine(
                model, params, num_slots=3, page_size=4, num_pages=32,
                max_len=24, prefill_chunk=5, mesh=mesh, speculative=spec)

        ref = engine().run(mk())            # non-spec single-device
        sref = engine(spec=sc).run(mk())    # spec single-device
        mesh = make_mesh((2, 4), ("data", "model"))
        seng = engine(mesh, sc)
        got = seng.run(mk())
        for i in range(3):
            np.testing.assert_array_equal(sref.results[i], got.results[i])
            if SP[i].is_greedy:
                np.testing.assert_array_equal(ref.results[i],
                                              got.results[i])
        assert seng._spec_draft._cache_size() == 1
        assert seng._spec_verify._cache_size() == 1
        # draft pools physically shard their KV-head axis over the mesh
        leaf = jax.tree.leaves(seng._draft_pools)[0]
        assert (leaf.addressable_shards[0].data.shape[-1]
                == leaf.shape[-1] // 4), leaf.sharding
        print("ok", got.spec_windows, round(got.accepted_per_window, 3))
    """)
    assert "ok" in out


def test_elastic_checkpoint_restore_across_meshes():
    """Checkpoint written from a (2,4) mesh restores onto a (4,2) mesh
    (elastic re-shard on restart) and training continues."""
    out = _run("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced_config
        from repro.models.model import build_model
        from repro.parallel.hints import sharding_rules
        from repro.parallel.plan import make_plan
        from repro.train import checkpoint as ckpt_lib
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import init_train_state, make_train_step

        cfg = reduced_config(get_config("qwen3-14b"))
        model = build_model(cfg)
        key = jax.random.PRNGKey(0)
        step = make_train_step(model, AdamWConfig(lr=1e-3))
        batch = {"tokens": jax.random.randint(key, (8, 32), 0,
                                              cfg.vocab_size)}
        ckpt_dir = tempfile.mkdtemp()

        mesh_a = make_mesh((2, 4), ("data", "model"))
        plan_a = make_plan(cfg, mesh_a, global_batch=8, shape_kind="train")
        state = init_train_state(model, key)
        with mesh_a, sharding_rules(plan_a.rules()):
            state, _ = jax.jit(step)(state, batch)
        ckpt_lib.save_checkpoint(ckpt_dir, 1, state)

        # "restart" on a different topology
        mesh_b = make_mesh((4, 2), ("data", "model"))
        plan_b = make_plan(cfg, mesh_b, global_batch=8, shape_kind="train")
        template = init_train_state(model, key)
        sh = type(template)(params=plan_b.param_shardings(template.params),
                            opt_state=plan_b.param_shardings(template.opt_state),
                            err=None)
        restored, step_no = ckpt_lib.restore_latest(ckpt_dir, template,
                                                    shardings=sh)
        assert step_no == 1
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(restored.params)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        with mesh_b, sharding_rules(plan_b.rules()):
            restored, m = jax.jit(step)(restored, batch)
        assert np.isfinite(float(m["loss"]))
        print("ok step", int(restored.step))
    """)
    assert "ok step 2" in out
