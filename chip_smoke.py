"""Chip smoke: serve phi3-mini-3.8b at its published widths on one TPU.

The quickest proof that the serving stack still starts on the chip.  It
drives the path users call — ``LLMEngine(backend="continuous")`` over the
paged KV pools and the Pallas paged-decode kernel — once, with bf16
weights generated from ``--seed`` (no checkpoint), and checks what comes
out:

  * the fused paged-decode kernel and the mxfp4 VMM kernel against their
    oracles at phi3 widths, within the bf16 tolerances below;
  * 16 requests (512-token prompts over 4 distinct prompts, so the prefix
    index is hit; 128 new tokens; greedy plus one sampled request) all
    finish with their token count and finite logprobs; the greedy streams
    repeat exactly when the same trace is served again; each greedy
    request's first-token logprob agrees with a dense (unpaged) forward
    pass;
  * the compiled decode step contains the Pallas kernel, and no mxfp4
    call fell back to its oracle.

  python chip_smoke.py              # one chip
  python chip_smoke.py --mesh 1x4   # tensor-parallel over four chips only

``--mesh 1x4`` serves the same greedy requests on one chip and then, after
freeing that engine, over a (1, 4) mesh with the accelerator's default
``reduce="psum"``, and compares the two.  With random weights the top
logits lie within a bf16 ulp of each other, and psum sums in another
order, so a stream may part at such a tie: the check is that the
logprobs agree up to and at the parting token.

Times printed are smoke timings of one cold run, not measurements.  The
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failed check exits non-zero without it.  With no TPU (or outside a
checkout of this repository) the script fails: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "phi3-mini-3.8b"
SLOTS, MAX_LEN, PAGE = 8, 2048, 16
N_REQUESTS, N_PROMPTS, PROMPT_LEN, MAX_TOKENS = 16, 4, 512, 128
MESH_TOKENS = 32            # greedy tokens compared between 1 and 4 chips
# bf16 tolerances: a bf16 result carries ~3 significant digits (2^-8)
ATTN_TOL = 1e-2             # paged decode output (|values| <~ 1)
VMM_RTOL = 2e-2             # mxfp4 VMM, relative to the output's max |value|
# logprob of one token from two code paths: the logits are bf16, one ulp
# is 2^-7 of their magnitude (0.0625 for |logit| in [8, 16)), and each
# path rounds its own — two such ulps
LOGPROB_TOL = 0.125


class Checks:
    """Collects named pass/fail results and prints each as it lands."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"[{'PASS' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def kernel_checks(check: Checks, cfg, seed: int) -> None:
    """Fused paged decode and mxfp4 VMM against their oracles, phi3 widths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.ops import paged_gqa_decode_attention
    from repro.kernels.mxfp4_vmm import ops as vmm_ops
    from repro.quant import formats

    key = jax.random.PRNGKey(seed)
    kvh, d, n_blocks = cfg.n_kv_heads, cfg.hd, MAX_LEN // PAGE
    n_pages = 1 + SLOTS * n_blocks
    # two layers of layer-stacked pools, as the scanned decode step holds
    # them; the check reads layer 1
    shape = (2, n_pages, PAGE, kvh * d)
    k_pages = jax.random.normal(jax.random.fold_in(key, 1), shape,
                                jnp.bfloat16)
    v_pages = jax.random.normal(jax.random.fold_in(key, 2), shape,
                                jnp.bfloat16)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                        .reshape(SLOTS, n_blocks), jnp.int32)
    pos = jnp.asarray(rng.integers(0, MAX_LEN, SLOTS), jnp.int32)
    q = jax.random.normal(key, (SLOTS, cfg.n_heads, d), jnp.bfloat16)
    layer = jnp.int32(1)
    fused = jax.jit(lambda *a: paged_gqa_decode_attention(
        *a, layer=layer, impl="fused"))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *a: paged_gqa_decode_attention(
            *a, layer=layer, impl="reference"))(q, k_pages, v_pages, table,
                                                pos)
    out = fused(q, k_pages, v_pages, table, pos)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    check("paged decode kernel vs oracle",
          bool(np.isfinite(err)) and err <= ATTN_TOL,
          f"({SLOTS} slots x {MAX_LEN} tokens, {kvh} KV heads x {d}) "
          f"max |err| {err:.3g} <= {ATTN_TOL}")
    del k_pages, v_pages

    k_in, n_out = cfg.d_model, cfg.d_ff
    w = formats.quantize(jax.random.normal(jax.random.fold_in(key, 3),
                                           (k_in, n_out), jnp.float32),
                         "mxfp4")
    x = jax.random.normal(jax.random.fold_in(key, 4), (SLOTS, k_in),
                          jnp.bfloat16)
    out = vmm_ops.mxfp4_matmul(x, w, impl="fused").astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = vmm_ops.mxfp4_matmul(x, w, impl="reference").astype(
            jnp.float32)
    scale = float(jnp.max(jnp.abs(ref)))
    err = float(jnp.max(jnp.abs(out - ref)))
    check("mxfp4 VMM kernel vs oracle",
          bool(np.isfinite(err)) and err <= VMM_RTOL * scale,
          f"({k_in}, {n_out}) max |err| {err:.3g} <= {VMM_RTOL} x "
          f"max |ref| {scale:.3g}")


class StepSpy:
    """Stands in for the engine's jitted decode step and keeps the abstract
    arguments of its first call, so the same program can be lowered and
    its compiled text inspected afterwards."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        import jax
        if self.args is None:
            self.args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), args)
        return self.fn(*args)


def make_engine(model, params, mesh=None):
    """``LLMEngine`` built the way ``repro.launch.serve`` builds it."""
    import jax.numpy as jnp

    from repro.runtime.llm import LLMEngine
    return LLMEngine(model, params, backend="continuous", max_len=MAX_LEN,
                     num_slots=SLOTS, page_size=PAGE,
                     num_pages=1 + SLOTS * (MAX_LEN // PAGE),
                     prefill_chunk=PROMPT_LEN, cache_dtype=jnp.bfloat16,
                     enable_prefix_cache=True, mesh=mesh, tp_reduce="auto",
                     speculative=None)


def make_traffic(cfg, seed: int, max_tokens: int, sampled: bool):
    from repro.runtime.sampling import SamplingParams
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (N_PROMPTS, PROMPT_LEN)).astype(np.int32)
    greedy = SamplingParams(max_tokens=max_tokens, logprobs=True)
    sps = [greedy] * N_REQUESTS
    if sampled:
        sps[-1] = SamplingParams(temperature=0.8, top_p=0.95, seed=seed + 1,
                                 max_tokens=max_tokens, logprobs=True)
    return prompts, [prompts[i % N_PROMPTS] for i in range(N_REQUESTS)], sps


def dense_first_logprobs(model, params, prompts):
    """Log-softmax at each prompt's last position from the dense (unpaged)
    forward pass — the reference for the engine's first tokens."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def last_logprobs(params, tokens):
        logits = model.forward(params, {"tokens": tokens})[:, -1]
        return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    return np.asarray(last_logprobs(params, jnp.asarray(prompts)))


def serve_checks(check: Checks, model, params, seed: int) -> None:
    """The one-chip serve phase: two passes of the same 16-request trace."""
    import jax

    from repro.kernels.mxfp4_vmm import ops as vmm_ops

    cfg = model.cfg
    prompts, req_prompts, sps = make_traffic(cfg, seed, MAX_TOKENS,
                                             sampled=True)
    dense = dense_first_logprobs(model, params, prompts)
    llm = make_engine(model, params)
    spy = StepSpy(llm._eng._step_fn)
    llm._eng._step_fn = spy
    runs, walls = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        runs.append(llm.generate(req_prompts, sps))
        walls.append(time.perf_counter() - t0)
        stats = llm.last_stats
        print(f"served {N_REQUESTS} requests: {stats.steps} decode steps, "
              f"{stats.chunks} prefill chunks, prefix-hit tokens "
              f"{stats.prefix_hit_tokens}/{stats.prompt_tokens}, "
              f"preemptions {stats.preemptions}", flush=True)
    first, second = runs
    for name, outs in (("first", first), ("second", second)):
        done = [o.finished and o.finish_reason == "length"
                and len(o.token_ids) == MAX_TOKENS for o in outs]
        check(f"{name} pass: every request finished with its tokens",
              all(done), f"{sum(done)}/{N_REQUESTS} x {MAX_TOKENS} tokens")
        lps = np.asarray([o.logprobs for o in outs], np.float64)
        check(f"{name} pass: logprobs finite",
              lps.shape == (N_REQUESTS, MAX_TOKENS)
              and bool(np.all(np.isfinite(lps))),
              f"shape {lps.shape}")
    greedy = [i for i, sp in enumerate(sps) if sp.is_greedy]
    same = [first[i].token_ids == second[i].token_ids for i in greedy]
    check("greedy streams repeat exactly", all(same),
          f"{sum(same)}/{len(greedy)} requests")
    check("prefix index hit", stats.prefix_hit_tokens > 0,
          f"{stats.prefix_hit_tokens} prompt tokens served from shared "
          f"pages")
    gaps = [abs(first[i].logprobs[0]
                - dense[i % N_PROMPTS][first[i].token_ids[0]])
            for i in greedy]
    check("greedy first-token logprobs match the dense forward",
          max(gaps) <= LOGPROB_TOL,
          f"max |paged - dense| {max(gaps):.3g} <= {LOGPROB_TOL}")
    text = spy.fn.lower(*spy.args).compile().as_text()
    check("decode step runs the Pallas kernel",
          "tpu_custom_call" in text, "tpu_custom_call in compiled HLO")
    check("no mxfp4 oracle fallback", vmm_ops.FALLBACK_STATS["fallback"] == 0,
          f"FALLBACK_STATS={dict(vmm_ops.FALLBACK_STATS)}")
    stats_dev = jax.devices()[0].memory_stats() or {}
    print(f"peak device bytes in use: {stats_dev.get('peak_bytes_in_use')}")
    print(f"smoke timings, not a metric: first pass (compiles included) "
          f"{walls[0]:.1f}s, second pass {walls[1]:.1f}s")


def greedy_run(model, params, mesh, seed: int):
    """Serve the greedy trace; returns (outputs, engine)."""
    _, req_prompts, sps = make_traffic(model.cfg, seed, MESH_TOKENS,
                                       sampled=False)
    llm = make_engine(model, params, mesh=mesh)
    return llm.generate(req_prompts, sps), llm


def mesh_checks(check: Checks, model, seed: int, n_model: int) -> None:
    """Greedy serving over a (1, n_model) mesh against one chip."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.parallel.plan import make_paged_serve_plan

    key = jax.random.PRNGKey(seed)
    params = jax.jit(model.init)(key)
    one, llm = greedy_run(model, params, None, seed)
    del params, llm                       # free the one-chip run first:
    gc.collect()                          # its weights and pools fill a chip
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    if in_use is not None:
        check("one-chip run freed", in_use < 2**30,
              f"{in_use} bytes left in use on device 0")

    mesh = make_mesh((1, n_model), ("data", "model"))
    plan = make_paged_serve_plan(model.cfg, mesh, reduce="auto")
    shapes = jax.eval_shape(model.init, key)
    # initialized sharded: the weights never sit whole on one device
    params = jax.jit(model.init,
                     out_shardings=plan.param_shardings(shapes))(key)
    tp, llm = greedy_run(model, params, mesh, seed)
    check("mesh reduce is the accelerator default",
          llm.serve_plan.reduce == "psum", f"reduce={llm.serve_plan.reduce}")
    # psum reassociates f32 sums, so bf16 activations can round apart and
    # a stream may part where two tokens' logits tie to within that noise;
    # up to the parting token (and at it, for each run's own choice) the
    # logprobs must agree
    equal, gaps = 0, []
    for a, b in zip(one, tp):
        n = next((i for i, (x, y) in enumerate(zip(a.token_ids, b.token_ids))
                  if x != y), MESH_TOKENS)
        equal += n == MESH_TOKENS
        upto = min(n + 1, MESH_TOKENS)
        gaps.append(max(abs(x - y) for x, y in zip(a.logprobs[:upto],
                                                   b.logprobs[:upto])))
        if n < MESH_TOKENS:
            print(f"request {a.rid}: streams part at token {n}: "
                  f"{a.token_ids[n]} (logprob {a.logprobs[n]:.4f}) on one "
                  f"chip vs {b.token_ids[n]} ({b.logprobs[n]:.4f}) on "
                  f"{n_model}")
    print(f"first {MESH_TOKENS} greedy tokens identical on 1 and {n_model} "
          f"chips: {equal}/{N_REQUESTS} requests")
    check(f"greedy streams on 1 and {n_model} chips agree up to ties",
          max(gaps) <= LOGPROB_TOL,
          f"max |logprob diff| {max(gaps):.3g} <= {LOGPROB_TOL}")
    per_device: dict = {}
    total = 0
    for leaf in jax.tree.leaves(llm._eng._pools):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (per_device.get(shard.device.id, 0)
                                           + shard.data.nbytes)
    print(f"KV pool bytes per device: {per_device} (total {total})")
    check(f"KV pools split evenly over {n_model} devices",
          len(per_device) == n_model
          and all(v * n_model == total for v in per_device.values()),
          f"{len(per_device)} devices x {total // n_model} bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", default=None, metavar="1xM",
                    help="serve over a (1, M) tensor-parallel mesh and "
                         "compare with one chip; nothing else runs")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax

    from repro.configs import get_config
    from repro.models.model import build_model

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    print(f"devices: {info}; compile cache {cache}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing "
              f"was run", file=sys.stderr)
        return 1
    check = Checks()
    cfg = get_config(ARCH)
    model = build_model(cfg)
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads x {cfg.hd}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size} (padded "
          f"{cfg.padded_vocab})", flush=True)
    if args.mesh:
        d, m = (int(x) for x in args.mesh.lower().split("x"))
        if d != 1 or m > len(devices):
            print(f"--mesh wants 1xM with M <= {len(devices)}",
                  file=sys.stderr)
            return 2
        mesh_checks(check, model, args.seed, m)
    else:
        kernel_checks(check, cfg, args.seed)
        t0 = time.perf_counter()
        params = jax.block_until_ready(
            jax.jit(model.init)(jax.random.PRNGKey(args.seed)))
        n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
        nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
        print(f"init: {n / 1e9:.3f} B parameters, {nbytes} bytes "
              f"(smoke timing, not a metric: {time.perf_counter() - t0:.1f}s)",
              flush=True)
        serve_checks(check, model, params, args.seed)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
