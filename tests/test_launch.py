"""Launch layer: dry-run cell in a clean subprocess (512 host devices),
multi-device EP correctness, and the train/serve driver entry points."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


def _run(code: str, timeout=900):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=ENV, capture_output=True, text=True,
                          timeout=timeout, cwd=REPO)


def test_dryrun_cell_subprocess():
    """One full dry-run cell: 512 host devices, 16x16 mesh, lower+compile,
    memory & roofline artifacts."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-370m",
         "--shape", "decode_32k", "--single-pod"],
        env=ENV, capture_output=True, text=True, timeout=900, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout and "dominant=" in r.stdout


def test_dryrun_skip_semantics():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "hubert-xlarge", "--shape", "decode_32k", "--single-pod"],
        env=ENV, capture_output=True, text=True, timeout=300, cwd=REPO)
    assert r.returncode == 0
    assert "SKIP" in r.stdout and "encoder-only" in r.stdout


def test_moe_ep_multidevice():
    """Expert-parallel MoE == dense reference on a real 2x4 device mesh."""
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.models import moe as moe_lib
        key = jax.random.PRNGKey(0)
        cfg = reduced_config(get_config('deepseek-v2-lite-16b'))
        p = moe_lib.init_moe(key, cfg)
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, 16, cfg.d_model), jnp.bfloat16)
        dense = moe_lib.moe_dense(x, p, cfg)
        mesh = make_mesh((2, 4), ('data', 'model'))
        with mesh:
            ep = jax.jit(lambda x, p: moe_lib.moe_ep(
                x, p, cfg, mesh, 'model',
                capacity_factor=float(cfg.n_experts)))(x, p)
        err = float(jnp.max(jnp.abs(ep.astype(np.float32)
                                    - dense.astype(np.float32))))
        assert err < 0.1, err
        print('ok', err)
    """)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok" in r.stdout


@pytest.fixture
def restore_cache_dir():
    """The entry points turn on the persistent compilation cache for their
    process; keep that from leaking into the rest of this test worker."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_dir_rule(monkeypatch, tmp_path, restore_cache_dir):
    """A set JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the cache
    goes to the fixed, git-ignored .jax_cache/ at the checkout root."""
    import jax
    from repro.launch import compile_cache
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.use_compile_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.slow
def test_train_launcher(tmp_path, restore_cache_dir):
    from repro.launch.train import main
    rc = main(["--arch", "qwen3-14b", "--steps", "6", "--batch", "2",
               "--seq", "32", "--ckpt-dir", str(tmp_path / "ckpt")])
    assert rc == 0


def test_serve_launcher(restore_cache_dir):
    from repro.launch.serve import main
    rc = main(["--arch", "h2o-danube-1.8b", "--batch", "2",
               "--prompt-len", "16", "--max-new", "8"])
    assert rc == 0


def test_cache_update_at_matches_dus():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.common import cache_update_at
    key = jax.random.PRNGKey(0)
    cache = jax.random.normal(key, (2, 16, 4, 8), jnp.bfloat16)
    new = jax.random.normal(jax.random.fold_in(key, 1), (2, 1, 4, 8),
                            jnp.bfloat16)
    for slot in (0, 7, 15):
        a = cache_update_at(cache, new, jnp.int32(slot))
        b = jax.lax.dynamic_update_slice(cache, new, (0, slot, 0, 0))
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
