"""Continuous batching vs static batch serving under Poisson arrivals.

The paper's throughput claim (18.6x over H100 at ISO-TDP) assumes decode
stays bandwidth-bound and **occupied**; with ragged request arrivals and
long-tail output lengths, a static batch engine idles finished slots until
the slowest request of the batch drains, and stalls new arrivals until a
whole batch forms.  This benchmark measures both engines on the same
request trace:

  * useful tokens/s   — sum over requests of their own generated tokens,
                        divided by wall time (compile excluded by warmup);
  * slot occupancy    — mean busy-slot fraction per decode iteration.

The static baseline is generous: it decodes each arrival-order batch only
to its **longest member's budget** (not a global cap), so the measured gap
is purely batch-formation waiting + idle finished slots — the two things
iteration-level admission removes.

Output lengths are drawn long-tail (clipped lognormal): most requests are
short, a few run to the cap — the reasoning-workload shape where batch
occupancy is the throughput lever (cf. LIMINAL / inference-scaling studies
in PAPERS.md).

A second workload measures **shared-prefix** traffic (N requests over M
distinct prompts — the multi-turn / system-prompt shape): prefix caching
shares a repeated prompt's full pages read-only and chunked prefill skips
straight to the first unseen token, so TTFT and prefill FLOPs drop against
the PR-1-style path (no sharing, whole-prompt admission).  The decode HBM
story is reported analytically per step: the gather-then-dense path reads
every K/V page, writes the dense copy, and reads it back (3x the pool
bytes); the gather-fused kernel streams each page exactly once.

Both engines run f32 params and f32 KV caches: XLA:CPU has no native bf16
GEMM and re-converts bf16 buffers around every step, which would swamp the
scheduling effect being measured here (on TPU both run bf16).

Both execution strategies are driven through the SAME ``LLMEngine``
request-level API (``generate(prompts, sampling_params)``) — the benchmark
compares backends, not entrypoints.

  PYTHONPATH=src python -m benchmarks.continuous_batching \
      [--batch 8] [--requests 64] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, dump
from repro.models.common import ModelConfig
from repro.models.model import build_model
from repro.runtime.llm import LLMEngine
from repro.runtime.sampling import SamplingParams

# Big enough that a fused decode step is compute/bandwidth-dominated on CPU
# (host dispatch noise < 5%), small enough to compile in seconds.
BENCH_CONFIG = ModelConfig(
    name="bench-serve", family="dense", n_layers=6, d_model=384,
    n_heads=8, n_kv_heads=4, head_dim=48, d_ff=1024, vocab_size=2048,
)

PROMPT_LEN = 16
MAX_NEW = 64          # per-request budget cap
PAGE = 40             # 2 blocks/request: paged gather width == dense width


def make_trace(n_req: int, seed: int, mean_interarrival: float):
    """Poisson arrivals, long-tail (clipped lognormal) output lengths."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(mean_interarrival, n_req))
    new_tokens = np.clip(rng.lognormal(np.log(6.0), 1.5, n_req).astype(int),
                         2, MAX_NEW)
    prompts = rng.integers(0, BENCH_CONFIG.vocab_size,
                           (n_req, PROMPT_LEN)).astype(np.int32)
    return arrivals, new_tokens, prompts


def run_static(model, params, arrivals, new_tokens, prompts, batch: int):
    """Arrival-order batches; each waits for full formation, then decodes to
    its longest member's budget (finished slots idle until then)."""
    llm = LLMEngine(model, params, backend="static",
                    max_len=PROMPT_LEN + MAX_NEW + 1,
                    cache_dtype=jnp.float32)
    n_req = prompts.shape[0]
    batches = [(lo, min(lo + batch, n_req))
               for lo in range(0, n_req, batch)]
    steps = [int(new_tokens[lo:hi].max()) for lo, hi in batches]
    shapes = {(hi - lo, n) for (lo, hi), n in zip(batches, steps)}
    for rows, n in sorted(shapes):         # compile each (rows, n_steps)
        llm.generate(list(prompts[:rows]), max_new_tokens=n)

    useful = 0
    t0 = time.monotonic()
    for (lo, hi), n in zip(batches, steps):
        wait = arrivals[hi - 1] - (time.monotonic() - t0)
        if wait > 0:                                  # batch not formed yet
            time.sleep(wait)
        llm.generate(list(prompts[lo:hi]),
                     [SamplingParams(max_tokens=int(t))
                      for t in new_tokens[lo:hi]])
        useful += int(new_tokens[lo:hi].sum())
    wall = time.monotonic() - t0
    return useful / wall, wall


def make_continuous_llm(model, params, batch: int) -> LLMEngine:
    return LLMEngine(
        model, params, backend="continuous", num_slots=batch, page_size=PAGE,
        num_pages=1 + 2 * batch * -(-(PROMPT_LEN + MAX_NEW) // PAGE),
        max_len=PROMPT_LEN + MAX_NEW, cache_dtype=jnp.float32,
        prefill_chunk=PROMPT_LEN)       # whole prompt in one chunk row


def run_continuous(model, params, arrivals, new_tokens, prompts, batch: int):
    llm = make_continuous_llm(model, params, batch)
    # warmup/compile: fused step + prefill/scatter at every pow-2 admission
    # bucket the run can hit
    b = 1
    while b <= batch:
        llm.generate([prompts[0]] * b, max_new_tokens=2)
        b *= 2

    llm.generate(list(prompts),
                 [SamplingParams(max_tokens=int(t)) for t in new_tokens],
                 arrival_times=[float(a) for a in arrivals])
    stats = llm.last_stats
    return stats.total_tokens / stats.wall, stats


# shared-prefix workload: prompts long enough to span several pages
SP_PROMPT_LEN = 96
SP_PAGE = 8
SP_MAX_NEW = 4


def decode_hbm_rows(mean_ctx: float) -> list[Row]:
    """Analytic decode-attention HBM traffic per generated token.

    The gather-fused kernel streams each live K/V page once
    (read-pool-only); the PR-1 gather-then-dense path reads the pool,
    writes the dense ``(B, S, KVH, D)`` copy, and reads it back in the
    kernel — 3x the bytes at equal context."""
    c = BENCH_CONFIG
    per_tok = 2 * mean_ctx * c.n_kv_heads * c.hd * 4 * c.n_layers  # K+V, f32
    fused = per_tok
    gather_dense = 3 * per_tok
    return [
        Row("ours:serving", "decode HBM bytes/token (gather-fused)",
            fused / 1e6, None, "MB",
            f"mean ctx {mean_ctx:.0f}, read each K/V page once"),
        Row("ours:serving", "decode HBM bytes/token (gather-then-dense)",
            gather_dense / 1e6, None, "MB",
            "PR-1 path: read pool + write dense + read dense"),
        Row("ours:serving", "fused decode HBM reduction", 3.0, None, "x",
            "paper's KV-stream argument: no dense intermediate"),
    ]


def run_shared_prefix(model, params, batch: int, n_req: int,
                      n_prompts: int, seed: int) -> list[Row]:
    """N requests over M distinct prompts: prefix caching + chunked prefill
    vs the PR-1-style path (no sharing, whole-prompt admission)."""
    max_len = SP_PROMPT_LEN + SP_MAX_NEW
    num_pages = 1 + 2 * batch * -(-max_len // SP_PAGE)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, BENCH_CONFIG.vocab_size,
                           (n_prompts, SP_PROMPT_LEN)).astype(np.int32)
    picks = np.arange(n_req) % n_prompts

    def make_engine(prefix: bool):
        return LLMEngine(
            model, params, backend="continuous", num_slots=batch,
            page_size=SP_PAGE, num_pages=num_pages, max_len=max_len,
            cache_dtype=jnp.float32,
            prefill_chunk=4 * SP_PAGE if prefix else SP_PROMPT_LEN,
            enable_prefix_cache=prefix)

    def warm(llm):
        # compile every pow-2 prefill-chunk bucket + the decode step (each
        # engine instance has its own jit caches, so warm per engine); the
        # staggered arrivals make later warm requests hit the prefix index,
        # compiling the short post-hit chunk width too
        b = 1
        while b <= batch:
            llm.generate([prompts[i % n_prompts] for i in range(b)],
                         max_new_tokens=2,
                         arrival_times=[0.2 * i for i in range(b)])
            b *= 2

    # calibrate arrival gaps to a decode step so prompts repeat while the
    # trace is still live (the regime prefix caching targets)
    probe = make_engine(True)
    warm(probe)
    t0 = time.monotonic()
    probe.generate([prompts[0]], max_new_tokens=8)
    step_s = (time.monotonic() - t0) / 8

    arrivals = [float(a) for a in np.cumsum(rng.exponential(8 * step_s, n_req))]
    trace_prompts = [prompts[picks[i]] for i in range(n_req)]

    def serve(llm):
        llm.generate(trace_prompts, max_new_tokens=SP_MAX_NEW,
                     arrival_times=arrivals)
        return llm.last_stats

    results = {}
    for name, prefix in (("prefix+chunked", True), ("pr1-style", False)):
        llm = make_engine(prefix)
        warm(llm)
        # best-of-2: wall-clock serving on a shared machine — keep the
        # least-interfered rep (same arrival trace both times)
        results[name] = min((serve(llm) for _ in range(2)),
                            key=lambda s: s.ttft_quantiles()[0])

    sp, s1 = results["prefix+chunked"], results["pr1-style"]
    p50, p99, pmean = sp.ttft_quantiles()
    q50, q99, qmean = s1.ttft_quantiles()
    mean_ctx = SP_PROMPT_LEN + SP_MAX_NEW / 2
    rows = [
        Row("ours:prefix", "prefix-cache hit rate", sp.prefix_hit_rate,
            None, "", f"{n_req} requests over {n_prompts} prompts"),
        Row("ours:prefix", "prefill tokens computed (prefix+chunked)",
            sp.prefill_tokens, None, "",
            f"of {sp.prompt_tokens} admitted ({sp.chunks} chunks)"),
        Row("ours:prefix", "prefill tokens computed (pr1-style)",
            s1.prefill_tokens, None, "", f"of {s1.prompt_tokens} admitted"),
        Row("ours:prefix", "prefill FLOPs saved",
            1.0 - sp.prefill_tokens / max(s1.prefill_tokens, 1), None, "",
            "fraction of prompt compute skipped via shared pages"),
        Row("ours:prefix", "TTFT p50 (prefix+chunked)", p50 * 1e3, None, "ms",
            f"vs {q50 * 1e3:.1f}ms pr1-style"),
        Row("ours:prefix", "TTFT p99 (prefix+chunked)", p99 * 1e3, None, "ms",
            f"vs {q99 * 1e3:.1f}ms pr1-style (admission interleaves with "
            "decode, so the running batch never stalls)"),
        Row("ours:prefix", "TTFT mean (prefix+chunked)", pmean * 1e3, None,
            "ms", f"vs {qmean * 1e3:.1f}ms pr1-style"),
        Row("ours:prefix", "TTFT p50 speedup", q50 / max(p50, 1e-9), None, "x",
            "prefix reuse skips shared full blocks"),
    ]
    return rows + decode_hbm_rows(mean_ctx)


# ---------------------------------------------------------------------------
# Capacity sweep (--capacity-sweep): the paper's capacity-vs-throughput
# trade-off on the REAL engine
# ---------------------------------------------------------------------------

# Fixed-bandwidth-interface HBM-CO stacks of growing capacity (the Fig 9/10
# provisioning axis, scaled to the toy model): the candidate's 256 GB/s
# interface (1 rank x 4 layers x 1 ch x 1 bank) at sub-array counts chosen
# so the derived KV budget crosses from cannot-fit-one-request, through
# preemption-storm, to knee-limited roomy (capacity = 32 x bank_mb MB).
# 0.37 (11.8MB) is the quantized-KV crossover: after the ~10.9MB exact
# mxfp4 weight bytes + workspace, the remainder backs one request's pages
# at fp8/int8 KV but not at f32 — the point the quant sweep serves and
# the f32 sweep reports "does not fit".
SWEEP_BANK_MBS = (0.15, 0.22, 0.25, 0.3, 0.37, 0.5, 1.0)


def run_capacity_sweep(model, params, n_req: int, seed: int,
                       bank_mbs=SWEEP_BANK_MBS,
                       cache_dtype=jnp.float32) -> list[Row]:
    """Serve the SAME greedy trace under DeploymentSpecs of growing HBM-CO
    capacity; report measured tokens/s and preemption rate against the
    spec's modeled roofline ceiling.

    Architectural assertions: outputs are byte-identical at every feasible
    point (restart-style preemption is invisible in the stream), and the
    derived pool grows monotonically with capacity.  Measured-vs-modeled
    is reported, not asserted — the model is the target hardware's memory
    roofline, the measurement is XLA:CPU.

    ``cache_dtype="fp8"`` / ``"int8"`` reruns the sweep with quantized KV
    page pools (weights execute mxfp4 either way): the derived pool gets
    ~4x the pages per MB, so stacks that "do not fit" under f32 KV serve
    the trace — the capacity knee of the sweep moves left.
    """
    from repro.core.hbmco import HBMCOConfig
    from repro.runtime.deployment import DeploymentError, DeploymentSpec

    max_len = PROMPT_LEN + MAX_NEW
    _, new_tokens, prompts = make_trace(n_req, seed, 0.0)  # all arrive at t0
    sps = [SamplingParams(max_tokens=int(t)) for t in new_tokens]
    tag = cache_dtype if isinstance(cache_dtype, str) \
        else jnp.dtype(cache_dtype).name
    group = f"ours:capacity[{tag}]" if isinstance(cache_dtype, str) \
        else "ours:capacity"

    rows: list[Row] = []
    ref_results = None
    last_pages = 0
    for mb in bank_mbs:
        hbm = HBMCOConfig(name=f"co-sweep-m{mb:g}", ranks=1,
                          channels_per_layer=1, banks_per_group=1,
                          bank_mb=mb)
        spec = DeploymentSpec(
            sku="rpu-cu", hbmco=hbm, stacks_per_device=1,
            weight_format="mxfp4", cache_dtype=cache_dtype,
            max_len=max_len, page_size=PAGE, prefill_chunk=PROMPT_LEN,
            max_slots=8, overcommit=2.0,
            mean_context=PROMPT_LEN + MAX_NEW // 2)
        try:
            llm = LLMEngine(model, params, backend="continuous", spec=spec)
        except DeploymentError as e:
            rows.append(Row(group,
                            f"{hbm.capacity_mb:.1f}MB stack measured tok/s",
                            0.0, None, "", f"does not fit: {e}"))
            continue
        dep = llm.deployment
        assert dep.num_pages >= last_pages, \
            "pool must grow monotonically with capacity"
        last_pages = dep.num_pages
        # warm every admission bucket the run can hit: pow-2 counts below
        # the slot count, plus a full-slots batch (whose prefill bucket is
        # pow2ceil(num_slots) — reachable even when num_slots is not a
        # power of two)
        b = 1
        while b < dep.num_slots:
            llm.generate([prompts[0]] * b, max_new_tokens=2)
            b *= 2
        llm.generate([prompts[0]] * dep.num_slots, max_new_tokens=2)
        outs = llm.generate(list(prompts), sps)
        stats = llm.last_stats
        results = [tuple(o.token_ids) for o in outs]
        if ref_results is None:
            ref_results = results
        else:
            assert results == ref_results, \
                "outputs must be byte-identical across capacity points"
        measured = stats.total_tokens / stats.wall
        preempt_rate = stats.preemptions / n_req
        cap = f"{hbm.capacity_mb:.1f}MB stack"
        rows.append(Row(
            group, f"{cap} measured tok/s", measured, None, "",
            f"{dep.num_pages} pages / {dep.num_slots} slots, "
            f"occupancy {stats.occupancy:.2f}, "
            f"{dep.kv_token_bytes}B KV/token ({tag})"))
        rows.append(Row(
            group, f"{cap} modeled ceiling",
            dep.tokens_per_s_ceiling, None, "tok/s",
            f"memory roofline at {dep.device.decode_bw / 1e9:.0f}GB/s "
            f"(target hardware, not the CPU host)"))
        rows.append(Row(
            group, f"{cap} preemptions/request", preempt_rate,
            None, "", f"{stats.preemptions} total over {n_req} requests"))
        rows.append(Row(
            group, f"{cap} KV budget",
            dep.kv_budget_bytes / 2**20, None, "MB",
            f"of {hbm.capacity_mb:.0f}MB after "
            f"{dep.weight_bytes_per_device / 2**20:.1f}MB mxfp4 weights + "
            f"{dep.workspace_bytes / 2**20:.1f}MB workspace; "
            f"{dep.modeled_j_per_token * 1e3:.2f} mJ/token modeled"))
    assert ref_results is not None, "no sweep point fit the model"
    return rows


# ---------------------------------------------------------------------------
# Tensor-parallel strong scaling (--mesh): a CPU-only rehearsal over 1 -> 8
# virtual host devices.  Each TP degree runs in a child process pinned to
# the CPU, so the sweep never competes for (or reports from) an
# accelerator; tensor-parallel serving on a chip is `chip_smoke.py --mesh`.
# ---------------------------------------------------------------------------

_MESH_WORKER = """
import os, json, sys, time, dataclasses
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(tp)d"
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, %(root)r)
from benchmarks.continuous_batching import (BENCH_CONFIG, MAX_NEW, PAGE,
                                            PROMPT_LEN, make_trace)
from repro.launch.mesh import make_mesh
from repro.models.model import build_model
from repro.runtime.engine import ContinuousServeEngine
from repro.runtime.sampling import SamplingParams
from repro.runtime.scheduler import Request

tp, n_req, batch, seed = %(tp)d, %(n_req)d, %(batch)d, %(seed)d
# 8 KV heads so every TP degree of the sweep divides the KV-head axis
cfg = dataclasses.replace(BENCH_CONFIG, name="bench-serve-tp", n_kv_heads=8)
model = build_model(cfg)
params = jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    model.init(jax.random.PRNGKey(seed)))
mesh = make_mesh((1, tp), ("data", "model")) if tp > 1 else None
eng = ContinuousServeEngine(
    model, params, num_slots=batch, page_size=PAGE,
    num_pages=1 + 2 * batch * -(-(PROMPT_LEN + MAX_NEW) // PAGE),
    max_len=PROMPT_LEN + MAX_NEW, cache_dtype=jnp.float32,
    prefill_chunk=PROMPT_LEN, mesh=mesh)
_, new_tokens, prompts = make_trace(n_req, seed, 0.0)
mk = lambda rs: [Request(rid=i, prompt=prompts[i],
                         max_new_tokens=int(new_tokens[i]),
                         sampling=SamplingParams(max_tokens=int(new_tokens[i])))
                 for i in rs]
eng.run(mk(range(min(batch, n_req))))           # warm/compile
stats = min((eng.run(mk(range(n_req))) for _ in range(2)),
            key=lambda s: s.wall)
plan = eng.serve_plan
print(json.dumps({
    "tp": tp,
    "tokens_per_s": stats.total_tokens / stats.wall,
    "steps": stats.steps,
    "kv_bytes_per_token_per_device": eng.kv_token_bytes_per_device(),
    "psum_bytes_per_step_per_device":
        plan.psum_bytes_per_step(model, batch) if plan else 0,
    "reduce": plan.reduce if plan else "none",
}))
"""


def run_mesh_sweep(n_req: int, batch: int, seed: int,
                   tps=(1, 2, 4, 8)) -> list[Row]:
    """CPU strong-scaling rehearsal over the TP degree, one CPU-pinned
    subprocess per point (each needs its own XLA host-device count and a
    clean compile cache).  CPU host devices share one socket, so tokens/s
    is a CPU smoke signal, not a device number; the architectural
    observables are per-device KV bytes/token (must shrink 1/TP — the
    paper's add-bandwidth-by-adding-CUs lever) and the per-step collective
    bytes the Megatron pairing costs."""
    import pathlib
    import subprocess
    import sys

    root = str(pathlib.Path(__file__).resolve().parents[1])
    results = []
    for tp in tps:
        code = _MESH_WORKER % {"tp": tp, "n_req": n_req, "batch": batch,
                               "seed": seed, "root": root}
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=1200,
                           env={**os.environ, "JAX_PLATFORMS": "cpu",
                                "PYTHONPATH": os.path.join(root, "src")})
        assert r.returncode == 0, r.stderr[-3000:]
        results.append(json.loads(r.stdout.strip().splitlines()[-1]))
    base = results[0]
    rows = []
    for res in results:
        tp = res["tp"]
        ratio = base["kv_bytes_per_token_per_device"] \
            / res["kv_bytes_per_token_per_device"]
        rows.append(Row("ours:tp-serving", f"tp={tp} CPU useful tok/s",
                        res["tokens_per_s"], None, "",
                        f"{res['steps']} steps, reduce={res['reduce']}"))
        rows.append(Row("ours:tp-serving", f"tp={tp} KV bytes/token/device",
                        res["kv_bytes_per_token_per_device"] / 1e3, None,
                        "KB", f"{ratio:.0f}x below tp=1 (expect {tp}x)"))
        rows.append(Row("ours:tp-serving", f"tp={tp} collective bytes/step",
                        res["psum_bytes_per_step_per_device"] / 1e3, None,
                        "KB", "per device, attention+MLP pair closes"))
        assert res["kv_bytes_per_token_per_device"] \
            == base["kv_bytes_per_token_per_device"] // tp, \
            "per-device KV bytes must scale 1/TP"
    return rows


def run(model, params, batch: int = 8, n_req: int = 64,
        seed: int = 0) -> list[Row]:
    # Calibrate the arrival rate to the hardware: mean interarrival = one
    # fused decode step, i.e. arrivals stagger at decode granularity (the
    # regime continuous batching targets) without starving either engine
    # for whole seconds.
    llm = LLMEngine(model, params, backend="static",
                    max_len=PROMPT_LEN + MAX_NEW + 1,
                    cache_dtype=jnp.float32)
    probe = [np.zeros((PROMPT_LEN,), np.int32)] * batch
    llm.generate(probe, max_new_tokens=16)
    t0 = time.monotonic()
    llm.generate(probe, max_new_tokens=16)
    step_s = (time.monotonic() - t0) / 16
    mean_interarrival = step_s

    arrivals, new_tokens, prompts = make_trace(n_req, seed, mean_interarrival)
    # best-of-2 per engine: the serving loops are wall-clock measurements on
    # a shared machine, so take the least-interfered rep (min-of-N timing)
    static_tps, static_wall = max(
        (run_static(model, params, arrivals, new_tokens, prompts, batch)
         for _ in range(2)), key=lambda r: r[0])
    cont_tps, stats = max(
        (run_continuous(model, params, arrivals, new_tokens, prompts, batch)
         for _ in range(2)), key=lambda r: r[0])
    speedup = cont_tps / static_tps
    rows = [
        Row("ours:serving", f"static batch={batch} useful tok/s",
            static_tps, None, "",
            f"wall {static_wall:.2f}s, decodes to max(batch budgets)"),
        Row("ours:serving", f"continuous slots={batch} useful tok/s",
            cont_tps, None, "",
            f"wall {stats.wall:.2f}s, {stats.steps} steps, "
            f"occupancy {stats.occupancy:.2f}, "
            f"{stats.preemptions} preemptions"),
        Row("ours:serving", "continuous / static speedup", speedup, None, "x",
            f"{n_req} requests, Poisson mean gap {mean_interarrival*1e3:.1f}ms, "
            f"lognormal lengths [2,{MAX_NEW}]"),
        Row("ours:serving", "mean slot occupancy", stats.occupancy, None, "",
            "busy slots / total slots per decode iteration"),
    ]
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--prompts", type=int, default=0,
                    help="distinct prompts for the shared-prefix workload "
                         "(default requests // 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-throughput", action="store_true",
                    help="run only the shared-prefix workload (faster)")
    ap.add_argument("--mesh", action="store_true",
                    help="CPU-only tensor-parallel rehearsal instead: "
                         "1 -> 8 virtual CPU devices, one CPU-pinned "
                         "subprocess per TP degree (CPU tokens/s, "
                         "per-device KV bytes/token, per-step collective "
                         "bytes)")
    ap.add_argument("--capacity-sweep", action="store_true",
                    help="DeploymentSpec capacity sweep instead: serve the "
                         "same trace under fixed-bandwidth HBM-CO stacks "
                         "of growing capacity (paper Fig 9/10 axis); "
                         "measured tokens/s + preemption rate vs the "
                         "modeled roofline ceiling, JSON artifact")
    ap.add_argument("--cache-dtype", default="f32",
                    choices=["f32", "fp8", "int8"],
                    help="KV pool dtype for --capacity-sweep; fp8/int8 "
                         "serve quantized page pools (mxfp4 weights either "
                         "way) and dump to capacity_sweep_quant")
    args = ap.parse_args(argv)
    if args.mesh:
        rows = run_mesh_sweep(args.requests, args.batch, args.seed)
        for r in rows:
            print(r.render())
        dump(rows, "continuous_batching_mesh")
        return 0
    if args.capacity_sweep:
        model = build_model(BENCH_CONFIG)
        params = jax.tree.map(
            lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
            model.init(jax.random.PRNGKey(args.seed)))
        cache_dtype = jnp.float32 if args.cache_dtype == "f32" \
            else args.cache_dtype
        rows = run_capacity_sweep(model, params, args.requests, args.seed,
                                  cache_dtype=cache_dtype)
        for r in rows:
            print(r.render())
        dump(rows, "capacity_sweep" if args.cache_dtype == "f32"
             else "capacity_sweep_quant")
        return 0
    model = build_model(BENCH_CONFIG)
    params = model.init(jax.random.PRNGKey(args.seed))
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)
    rows = [] if args.skip_throughput else run(model, params, args.batch,
                                               args.requests, args.seed)
    rows += run_shared_prefix(model, params, args.batch, args.requests,
                              args.prompts or max(args.requests // 4, 1),
                              args.seed)
    for r in rows:
        print(r.render())
    dump(rows, "continuous_batching")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
