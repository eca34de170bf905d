"""Paper Fig 14: speculative decoding — analytic RPU point + a MEASURED
draft/target comparison on the real continuous engine.

``run()`` (used by ``benchmarks.run``) keeps the paper-anchored analytic
rows: Llama3-70B target / Llama3-8B draft on the RPU-200CU roofline
(8-token lookahead, 4.6 accepted/window, 1.8x).

``main()`` measures the scheduler-integrated speculative mode end to end
on XLA:CPU (f32): the SAME Poisson-free greedy trace served by the
continuous engine with and without a draft.  The draft is the target's
own first ``--draft-layers`` layers (sliced stacked weights, shared
embed/head); the target's deeper blocks are damped (out-projections
scaled by ``--damp``) so the draft agrees with the target often enough
to measure a real speedup — the same high-acceptance regime the paper's
Fig 14 assumes, scaled to a toy model.  Greedy speculation is lossless,
so the benchmark also ASSERTS byte-identical outputs between the two
engines; ``--assert-speedup`` additionally gates on >= 1.3x useful
tokens/s (the slow CI tier runs this).

Measured accepted-per-window is reported against the DeploymentSpec
window model evaluated AT the measured per-token acceptance rate
(``alpha(1-alpha^g)/(1-alpha)`` — i.i.d. acceptance assumption), so the
JSON artifact carries modeled-vs-measured for both throughput and
acceptance.

  PYTHONPATH=src python -m benchmarks.spec_decode [--gamma 4] \
      [--requests 12] [--assert-speedup]
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, dump
from repro.configs import get_config
from repro.models.common import ModelConfig
from repro.models.model import build_model
from repro.runtime.deployment import DeploymentSpec
from repro.runtime.llm import LLMEngine
from repro.runtime.sampling import SamplingParams
from repro.runtime.speculative import SpeculativeConfig
from repro.sim.scaling import rpu_point

PUBLISHED_TOKENS_PER_S = {
    "NVIDIA H200": 134, "SambaNova": 457, "Groq LPU": 1678,
    "Cerebras WSE-3": 2148, "RPU (paper)": 4423,
}


def run() -> list[Row]:
    """Analytic Fig 14 rows on the RPU roofline (paper's window stats)."""
    cfg70 = get_config("llama3-70b")
    cfg8 = get_config("llama3-8b")
    # RPU-200CU base decode latency for the 70B target + 8B draft steps.
    p70 = rpu_point(cfg70, 200, batch=1, seq_len=8192)
    p8 = rpu_point(cfg8, 200, batch=1, seq_len=8192)
    gamma, accepted = 8, 4.6                      # paper's window stats
    # one window: gamma draft steps + 1 target verification pass (the
    # verification VMM streams the same weights once — like one target step)
    window_s = gamma * p8.ms_per_token * 1e-3 + p70.ms_per_token * 1e-3
    toks_per_s = accepted / window_s
    base_tps = 1e3 / p70.ms_per_token
    rows = [
        Row("Fig14", "RPU-200CU 70B base decode", base_tps, None, " tok/s"),
        Row("Fig14", "RPU-200CU speculative throughput", toks_per_s, 4423,
            " tok/s", f"{gamma}-lookahead, {accepted} accepted"),
        Row("Fig14", "speculative speedup", toks_per_s / base_tps, 1.8, "x"),
    ]
    for sys_name, tps in PUBLISHED_TOKENS_PER_S.items():
        rows.append(Row("Fig14", f"published: {sys_name}", tps, None,
                        " tok/s"))
    rows.append(Row("Fig14", "RPU(ours)/best-competitor",
                    toks_per_s / 2148, 4423 / 2148, "x", "vs Cerebras WSE-3"))
    return rows


# ---------------------------------------------------------------------------
# Measured: the real continuous engine, spec vs non-spec, same trace
# ---------------------------------------------------------------------------

PROMPT_LEN = 16
PAGE = 40             # 2 blocks/request at max_len 64


def bench_config(n_layers: int) -> ModelConfig:
    return ModelConfig(
        name="bench-spec", family="dense", n_layers=n_layers, d_model=384,
        n_heads=8, n_kv_heads=4, head_dim=48, d_ff=1024, vocab_size=2048)


def _damp_deep_blocks(params, keep: int, eps: float):
    """Scale the residual out-projections of blocks >= ``keep`` by
    ``eps``: the deep layers barely move the hidden state, so the
    truncated draft's argmax tracks the target's."""
    def go(d):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = go(v)
            elif k in ("wo", "w_down"):
                out[k] = v.at[keep:].multiply(eps)
            else:
                out[k] = v
        return out
    params = dict(params)
    params["stacks"] = [tuple(go(blk) for blk in stack)
                        for stack in params["stacks"]]
    return params


def build_pair(n_layers: int, draft_layers: int, damp: float, seed: int):
    """Target + draft sharing weights: the draft IS the target's first
    ``draft_layers`` layers (stacked-leaf slices) with the same
    embed/head, so draft cost ~ draft_layers/n_layers of a target step."""
    cfg = bench_config(n_layers)
    model = build_model(cfg)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        model.init(jax.random.PRNGKey(seed)))
    params = _damp_deep_blocks(params, draft_layers, damp)
    dcfg = dataclasses.replace(cfg, name=cfg.name + "-draft",
                               n_layers=draft_layers)
    draft = build_model(dcfg)
    dparams = dict(params)
    dparams["stacks"] = jax.tree.map(lambda a: a[:draft_layers],
                                     params["stacks"])
    return model, params, draft, dparams


def run_measured(gamma: int, slots: int, n_req: int, max_new: int,
                 n_layers: int, draft_layers: int, damp: float,
                 seed: int, reps: int = 2) -> tuple[list[Row], float]:
    model, params, draft, dparams = build_pair(n_layers, draft_layers,
                                               damp, seed)
    # + gamma: verify windows scatter KV past the last emitted token
    max_len = PROMPT_LEN + max_new + gamma
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, model.cfg.vocab_size,
                           (n_req, PROMPT_LEN)).astype(np.int32)

    def make(spec_cfg):
        return LLMEngine(
            model, params, backend="continuous", num_slots=slots,
            page_size=PAGE, num_pages=1 + 2 * slots * -(-max_len // PAGE),
            max_len=max_len, cache_dtype=jnp.float32,
            prefill_chunk=PROMPT_LEN, speculative=spec_cfg)

    base = make(None)
    spec = make(SpeculativeConfig(draft_model=draft, draft_params=dparams,
                                  gamma=gamma))
    for llm in (base, spec):
        b = 1                 # compile every pow-2 admission bucket
        while b <= slots:
            llm.generate([prompts[0]] * b, max_new_tokens=2)
            b *= 2

    def serve(llm):
        outs = llm.generate(list(prompts), max_new_tokens=max_new)
        return llm.last_stats, [tuple(o.token_ids) for o in outs]

    # best-of-N: wall-clock on a shared machine, keep the least-interfered
    (bstats, bres) = min((serve(base) for _ in range(reps)),
                         key=lambda r: r[0].wall)
    (sstats, sres) = min((serve(spec) for _ in range(reps)),
                         key=lambda r: r[0].wall)
    assert bres == sres, \
        "greedy speculation must be byte-identical to the plain engine"

    base_tps = bstats.total_tokens / bstats.wall
    spec_tps = sstats.total_tokens / sstats.wall
    speedup = spec_tps / base_tps
    alpha = sstats.spec_accepted / max(sstats.spec_drafted, 1)
    # the DeploymentSpec window model AT the measured acceptance rate
    dep = DeploymentSpec(sku="rpu-cu", max_len=max_len, page_size=PAGE,
                         max_slots=slots).resolve(
        model, draft=draft, draft_params=dparams, gamma=gamma,
        spec_accept_rate=alpha)
    plain_dep = DeploymentSpec(sku="rpu-cu", max_len=max_len,
                               page_size=PAGE, max_slots=slots).resolve(model)
    modeled_speedup = (dep.spec_tokens_per_s_ceiling
                       / plain_dep.tokens_per_s_ceiling)
    rows = [
        Row("ours:spec", f"non-spec slots={slots} useful tok/s", base_tps,
            None, "", f"wall {bstats.wall:.2f}s, {bstats.steps} steps"),
        Row("ours:spec", f"speculative gamma={gamma} useful tok/s", spec_tps,
            None, "",
            f"wall {sstats.wall:.2f}s, {sstats.spec_windows} windows, "
            f"draft {draft_layers}/{n_layers} layers"),
        Row("ours:spec", "measured speedup", speedup, None, "x",
            f"{n_req} greedy requests, byte-identical outputs"),
        Row("ours:spec", "accepted/window (measured)",
            sstats.accepted_per_window, None, "",
            f"of gamma={gamma} drafted; {sstats.spec_wasted} draft "
            f"tokens wasted"),
        Row("ours:spec", "accepted/window (modeled)",
            dep.spec_expected_accepted, None, "",
            f"alpha(1-alpha^g)/(1-alpha) at measured alpha={alpha:.3f}"),
        Row("ours:spec", "per-token acceptance rate", alpha, None, "",
            "accepted draft proposals / drafted"),
        Row("ours:spec", "modeled window speedup (RPU roofline)",
            modeled_speedup, None, "x",
            f"{dep.spec_window_seconds * 1e6:.1f}us window vs "
            f"{plain_dep.step_seconds * 1e6:.1f}us step on "
            "target hardware (not the CPU host)"),
    ]
    return rows, speedup


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--draft-layers", type=int, default=2)
    ap.add_argument("--damp", type=float, default=0.005,
                    help="scale on deep-block out-projections (lower = "
                         "higher draft/target agreement)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--assert-speedup", type=float, nargs="?",
                    const=1.3, default=None,
                    help="fail unless measured speedup >= this (CI gate; "
                         "default 1.3 when given without a value)")
    args = ap.parse_args(argv)
    t0 = time.time()
    rows, speedup = run_measured(
        args.gamma, args.slots, args.requests, args.max_new, args.layers,
        args.draft_layers, args.damp, args.seed, args.reps)
    rows += run()                      # analytic paper anchor in the same JSON
    for r in rows:
        print(r.render())
    dump(rows, "spec_decode")
    print(f"[{time.time() - t0:.1f}s] speedup {speedup:.2f}x "
          f"-> experiments/bench_spec_decode.json")
    if args.assert_speedup is not None:
        assert speedup >= args.assert_speedup, \
            f"speculative speedup {speedup:.2f}x < {args.assert_speedup}x"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
