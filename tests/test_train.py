"""Training loop: convergence, checkpoint/restart, fault injection."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.data.pipeline import SyntheticTokenPipeline
from repro.launch.mesh import make_mesh
from repro.models.model import build_model
from repro.train import checkpoint as ckpt_lib
from repro.train.loop import LoopConfig, run_training
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state, lr_schedule
from repro.train.train_step import TrainState, init_train_state, make_train_step


@pytest.fixture()
def tiny():
    cfg = reduced_config(get_config("h2o-danube-1.8b"))
    model = build_model(cfg)
    return cfg, model


def _pipeline(cfg, batch=4, seq=32):
    return SyntheticTokenPipeline(cfg, global_batch=batch, seq_len=seq)


def test_loss_decreases(tiny, tmp_path):
    cfg, model = tiny
    step_fn = make_train_step(model, AdamWConfig(lr=1e-3, warmup_steps=2,
                                                 total_steps=40))
    state = init_train_state(model, jax.random.PRNGKey(0))
    res = run_training(step_fn, state, _pipeline(cfg),
                       LoopConfig(total_steps=30, ckpt_every=100,
                                  ckpt_dir=str(tmp_path)))
    first = np.mean(res.losses[:5])
    last = np.mean(res.losses[-5:])
    assert last < first - 0.05, (first, last)


def test_checkpoint_resume_bitexact(tiny, tmp_path):
    """20 straight steps == 10 steps + restart + 10 steps (same data)."""
    cfg, model = tiny
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    step_fn = make_train_step(model, opt)

    sA = init_train_state(model, jax.random.PRNGKey(0))
    resA = run_training(step_fn, sA, _pipeline(cfg),
                        LoopConfig(total_steps=20, ckpt_every=100,
                                   ckpt_dir=str(tmp_path / "a")))

    sB = init_train_state(model, jax.random.PRNGKey(0))
    run_training(step_fn, sB, _pipeline(cfg),
                 LoopConfig(total_steps=10, ckpt_every=10,
                            ckpt_dir=str(tmp_path / "b")))
    sB2 = init_train_state(model, jax.random.PRNGKey(0))   # fresh process
    resB = run_training(step_fn, sB2, _pipeline(cfg),
                        LoopConfig(total_steps=20, ckpt_every=10,
                                   ckpt_dir=str(tmp_path / "b")))
    assert resB.resumed_from == 10
    for a, b in zip(jax.tree.leaves(resA.state.params),
                    jax.tree.leaves(resB.state.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0, rtol=0)


def test_injected_failure_recovers(tiny, tmp_path):
    cfg, model = tiny
    step_fn = make_train_step(model, AdamWConfig(lr=1e-3, total_steps=30))
    state = init_train_state(model, jax.random.PRNGKey(0))
    fired = {"n": 0}

    def fail_once(step):
        if step == 15 and fired["n"] == 0:
            fired["n"] += 1
            return True
        return False

    res = run_training(step_fn, state, _pipeline(cfg),
                       LoopConfig(total_steps=20, ckpt_every=5,
                                  ckpt_dir=str(tmp_path)),
                       failure_fn=fail_once)
    assert res.rollbacks == 1
    assert int(res.state.step) == 20


def test_failure_before_checkpoint_raises(tiny, tmp_path):
    cfg, model = tiny
    step_fn = make_train_step(model, AdamWConfig())
    state = init_train_state(model, jax.random.PRNGKey(0))
    with pytest.raises(RuntimeError):
        run_training(step_fn, state, _pipeline(cfg),
                     LoopConfig(total_steps=10, ckpt_every=50,
                                ckpt_dir=str(tmp_path)),
                     failure_fn=lambda s: s == 3)


def test_checkpoint_atomicity(tiny, tmp_path):
    """Interrupted (partial) checkpoint directories are never listed."""
    cfg, model = tiny
    state = init_train_state(model, jax.random.PRNGKey(0))
    ckpt_lib.save_checkpoint(str(tmp_path), 5, state)
    # fake a torn write: tmp dir left behind
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert ckpt_lib.list_checkpoints(str(tmp_path)) == [5]


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in range(0, 101, 5)]
    assert lrs[0] == 0.0
    assert max(lrs) == pytest.approx(1.0, rel=0.05)
    assert lrs[-1] == pytest.approx(0.1, rel=0.05)


def test_bf16_opt_state_dtype(tiny):
    cfg, model = tiny
    params = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params, "bfloat16")
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(opt["m"]))
    g = jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32), params)
    new_p, new_opt, _ = adamw_update(
        AdamWConfig(state_dtype="bfloat16"), params, g, opt)
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(new_opt["v"]))


def test_grad_compression_train_step_runs(tiny):
    """shard_map cross-pod compression path traces and runs on a 1-'pod'
    mesh (numerical path identical to DP mean when pods=1)."""
    cfg, model = tiny
    mesh = make_mesh((1,), ("pod",))
    opt = AdamWConfig(lr=1e-3)
    step_fn = make_train_step(model, opt, compress_pods=True, mesh=mesh)
    state = init_train_state(model, jax.random.PRNGKey(0), n_pods=1)
    batch = _pipeline(cfg).get_batch(0)
    batch = jax.tree.map(jnp.asarray, batch)
    with mesh:
        new_state, metrics = jax.jit(step_fn)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
