"""Training launcher.

Runs a real (small-scale) training job with the same code paths the
production mesh uses: sharded params via ``ParallelPlan``, fault-tolerant
loop (checkpoint / NaN rollback / resume), host-sharded data pipeline.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --reduced \
      --steps 100 --batch 8 --seq 128

On a real TPU slice the same entry point is used with --no-reduced and the
production mesh; this container runs the reduced config on CPU.
"""
from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced_config
from repro.data.pipeline import SyntheticTokenPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_small_mesh
from repro.models.model import build_model
from repro.parallel.hints import sharding_rules
from repro.parallel.plan import make_plan
from repro.train.loop import LoopConfig, run_training
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--no-reduced", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    model = build_model(cfg)

    mesh = make_small_mesh()
    plan = make_plan(cfg, mesh, global_batch=args.batch, shape_kind="train")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)
    step_fn = make_train_step(model, opt_cfg, remat=args.remat)
    state = init_train_state(model, jax.random.PRNGKey(args.seed))

    pipeline = SyntheticTokenPipeline(
        cfg, global_batch=args.batch, seq_len=args.seq, seed=args.seed)

    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                          ckpt_dir=args.ckpt_dir, log_every=10)

    with mesh, sharding_rules(plan.rules()):
        result = run_training(step_fn, state, pipeline, loop_cfg)

    n = model.param_count(result.state.params)
    if result.losses:
        span = (f"first_loss={result.losses[0]:.4f} "
                f"last_loss={result.losses[-1]:.4f}")
    else:
        span = f"(resumed at step {result.resumed_from}: already complete)"
    print(f"arch={cfg.name} params={n/1e6:.1f}M steps={len(result.losses)} "
          f"{span} rollbacks={result.rollbacks}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
