"""Serving launcher: one ``LLMEngine`` front-end, three backends.

``--backend static`` runs the whole decode as ONE jitted ``lax.scan`` (no
per-token host dispatch) — the JAX analogue of the RPU's host-free
execution model.  ``--backend continuous`` (also ``--continuous``) runs
iteration-level batching over the block-paged KV cache: requests arrive as
a Poisson process (``--arrival-rate`` req/s) and are admitted into freed
decode slots without recompiling.  ``--backend speculative`` (also
``--speculative``) runs draft/target speculative decoding (paper Fig 14)
with a reduced draft model.

Per-request generation is a ``SamplingParams``: ``--temperature``,
``--top-k``, ``--top-p``, ``--min-p``, ``--stop-token`` (repeatable), and
``--seed`` apply to every request; ``--sampling-mix`` serves a
heterogeneous mix instead (comma-separated ``temp:top_p[:top_k]`` specs
cycled across requests — all of them share the ONE compiled decode step,
since per-slot sampling params are data, not shapes).

Continuous admission runs **chunked prefill** (``--prefill-chunk`` tokens
per iteration per request) interleaved with decode, and shares prompt
prefixes through the page pool's prefix index (``--num-prompts`` distinct
prompts over ``--num-requests`` requests exercises the sharing;
``--no-prefix-cache`` disables it).  ``--spec-draft reduced --gamma 4``
turns on scheduler-integrated speculative decoding inside the continuous
engine: each occupied slot drafts gamma tokens with a reduced model over
its own paged KV pool, the target verifies them in one multi-token decode
step, and the end-of-run summary reports windows / accepted-per-window /
wasted draft tokens.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \
      --batch 4 --prompt-len 64 --max-new 32 [--backend speculative]
  PYTHONPATH=src python -m repro.launch.serve --continuous \
      --num-requests 16 --arrival-rate 50 --batch 4 --num-prompts 4 \
      --sampling-mix 0.0:1.0,0.8:0.9:40,1.0:0.95
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_small_mesh, parse_mesh
from repro.models.model import build_model
from repro.parallel.hints import sharding_rules
from repro.parallel.plan import make_plan
from repro.quant import formats
from repro.runtime.deployment import DeploymentSpec
from repro.runtime.llm import LLMEngine
from repro.runtime.sampling import SamplingParams

# "fp8" / "int8" are the quantized page pools from repro.quant.kv: codes in
# the narrow dtype + per-token-per-KV-head f32 scales riding in the pool.
CACHE_DTYPES = {"bf16": jnp.bfloat16, "f16": jnp.float16, "f32": jnp.float32,
                "fp8": "fp8", "int8": "int8"}


def parse_mix(spec: str, base: SamplingParams) -> list[SamplingParams]:
    """``temp:top_p[:top_k]`` specs, comma-separated, cycled per request."""
    out = []
    for part in spec.split(","):
        fields = part.split(":")
        if not 2 <= len(fields) <= 3:
            raise ValueError(f"bad --sampling-mix entry {part!r} "
                             "(want temp:top_p[:top_k])")
        out.append(SamplingParams(
            temperature=float(fields[0]), top_p=float(fields[1]),
            top_k=int(fields[2]) if len(fields) == 3 else 0,
            min_p=base.min_p, seed=base.seed,
            stop_token_ids=base.stop_token_ids))
    return out


def main(argv=None) -> int:
    import sys
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--fleet" in argv:
        # fleet-level serving (router + simulator + autoscaler) has its
        # own argument surface — delegate everything else to it
        argv.remove("--fleet")
        from repro.launch.fleet import main as fleet_main
        return fleet_main(argv)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--no-reduced", dest="reduced", action="store_false")
    ap.add_argument("--backend", default=None,
                    choices=["static", "continuous", "speculative"])
    ap.add_argument("--continuous", action="store_true",
                    help="alias for --backend continuous")
    ap.add_argument("--speculative", action="store_true",
                    help="alias for --backend speculative")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    # -- per-request sampling -------------------------------------------
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--min-p", type=float, default=0.0)
    ap.add_argument("--stop-token", type=int, action="append", default=[],
                    help="finish a request when this token id is emitted "
                         "(repeatable)")
    ap.add_argument("--sampling-mix", default=None,
                    help="comma-separated temp:top_p[:top_k] specs cycled "
                         "across requests (heterogeneous per-slot mix "
                         "through one compiled decode step)")
    # -- continuous-batching knobs --------------------------------------
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson request arrival rate in req/s "
                         "(0 = all requests arrive at t=0)")
    ap.add_argument("--num-requests", type=int, default=0,
                    help="total requests for continuous (default 3x batch)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size in tokens for continuous")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prefill chunk size in tokens for continuous")
    ap.add_argument("--num-prompts", type=int, default=0,
                    help="distinct prompts for continuous (0 = all "
                         "distinct; lower values share prefixes)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false", default=True,
                    help="disable prompt-prefix page sharing")
    ap.add_argument("--spec-draft", default=None,
                    choices=["self", "reduced"],
                    help="scheduler-integrated speculative decoding for the "
                         "continuous backend: 'reduced' drafts with an "
                         "n_layers/4 copy of the target, 'self' with the "
                         "target itself (acceptance ~1; a plumbing check)")
    ap.add_argument("--gamma", type=int, default=4,
                    help="draft lookahead per speculative window")
    ap.add_argument("--disaggregate", default=None, metavar="P:D",
                    help="phase-split continuous serving behind the KV-page "
                         "handoff: with --mesh, prefill runs on the first P "
                         "and decode on the next D device slices of the "
                         "model axis (P+D <= its size); without --mesh the "
                         "two phase engines share the host device")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="shard the continuous serve path over a "
                         "(data=D, model=M) mesh: KV page pools split "
                         "per KV head over the model axis (e.g. --mesh 2x4 "
                         "with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--tp-reduce", default="auto",
                    choices=["auto", "gather", "psum"],
                    help="how each Megatron column pair closes on the mesh: "
                         "gather = bit-exact all-gather composition (CPU "
                         "default), psum = one f32 psum per attention/MLP "
                         "block (accelerator default)")
    # -- hardware-aware deployment (DeploymentSpec) ----------------------
    ap.add_argument("--sku", default=None,
                    help="deployment hardware point: rpu-cu | tpu-v5e | "
                         "h100 | h200.  Giving --sku/--hbmco/--weight-"
                         "format switches the engine to the DeploymentSpec "
                         "path: KV pool pages and decode slots are derived "
                         "from the per-device memory budget and the "
                         "bandwidth roofline instead of --batch")
    ap.add_argument("--hbmco", default=None,
                    help="HBM-CO memory stack: hbm3e-like | hbmco-768MB | "
                         "co-r<R>c<C>b<B>m<MB> (paper Fig-5 design-space "
                         "naming)")
    ap.add_argument("--weight-format", default=None,
                    choices=sorted(formats.FORMATS),
                    help="block-quantized weight format for the capacity "
                         "budget (the RPU streams compressed weights, §V)")
    ap.add_argument("--cache-dtype", default=None,
                    choices=sorted(CACHE_DTYPES),
                    help="KV page-pool dtype (default: engine default); "
                         "fp8/int8 store quantized codes + per-token scales "
                         "in the pool (continuous backend only)")
    ap.add_argument("--max-slots", type=int, default=32,
                    help="cap on the spec-derived decode slot count")
    ap.add_argument("--seed", type=int, default=0,
                    help="model-init seed AND per-request sampling seed")
    args = ap.parse_args(argv)
    use_compile_cache()
    backend = args.backend or ("continuous" if args.continuous else
                               "speculative" if args.speculative else
                               "static")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    if not cfg.has_decode:
        print(f"{cfg.name} is encoder-only: no decode step")
        return 1
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)

    serve_mesh = parse_mesh(args.mesh) if args.mesh else None
    if serve_mesh is not None and backend != "continuous":
        print("--mesh shards the continuous backend; "
              f"ignoring it for backend={backend}")
        serve_mesh = None
    disagg = None
    if args.disaggregate:
        if backend != "continuous":
            print("--disaggregate splits the continuous backend; "
                  f"ignoring it for backend={backend}")
        else:
            try:
                p_dev, d_dev = (int(x) for x in args.disaggregate.split(":"))
            except ValueError:
                print(f"--disaggregate wants 'P:D', got "
                      f"{args.disaggregate!r}")
                return 1
            disagg = (p_dev, d_dev)
    pmesh = dmesh = serve_mesh
    if disagg is not None and serve_mesh is not None:
        from repro.parallel.plan import split_mesh
        pmesh, dmesh = split_mesh(serve_mesh, disagg[0], disagg[1])
    # the ambient mesh of the unsharded paths is ONE device: serving
    # without --mesh must not spread itself over every visible chip
    mesh = make_small_mesh(1)
    plan = make_plan(cfg, mesh, global_batch=args.batch, shape_kind="decode")
    max_len = args.prompt_len + args.max_new + 1
    if args.spec_draft is not None and backend == "continuous":
        # verify windows may overshoot by up to gamma draft positions
        # before rollback, so slots need that much page headroom
        max_len += args.gamma

    cache_dtype = CACHE_DTYPES.get(args.cache_dtype)
    spec = None
    if args.sku or args.hbmco or args.weight_format:
        spec = DeploymentSpec(
            sku=args.sku or "rpu-cu", hbmco=args.hbmco,
            mesh=serve_mesh, tp_reduce=args.tp_reduce,
            weight_format=args.weight_format, cache_dtype=cache_dtype,
            max_len=max_len, page_size=args.page_size,
            prefill_chunk=args.prefill_chunk, max_slots=args.max_slots)

    base = SamplingParams(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        min_p=args.min_p, seed=args.seed,
        stop_token_ids=tuple(args.stop_token))

    spec_cfg = None
    if args.spec_draft is not None:
        if backend != "continuous":
            print(f"--spec-draft configures the continuous backend; "
                  f"ignoring it for backend={backend}")
        else:
            import dataclasses

            from repro.runtime.speculative import SpeculativeConfig
            if args.spec_draft == "reduced":
                draft_cfg = dataclasses.replace(
                    cfg, name=cfg.name + "-draft",
                    n_layers=max(2, cfg.n_layers // 4))
                draft = build_model(draft_cfg)
                spec_cfg = SpeculativeConfig(
                    draft_model=draft,
                    draft_params=draft.init(jax.random.fold_in(key, 3)),
                    gamma=args.gamma)
            else:                        # "self": target drafts for itself
                spec_cfg = SpeculativeConfig(gamma=args.gamma)

    with mesh, sharding_rules(plan.rules()):
        if backend == "continuous":
            n_req = args.num_requests or 3 * args.batch
            rng = np.random.default_rng(args.seed)
            gaps = (rng.exponential(1.0 / args.arrival_rate, n_req)
                    if args.arrival_rate > 0 else np.zeros(n_req))
            arrivals = np.cumsum(gaps)
            n_distinct = args.num_prompts or n_req
            pool_prompts = np.asarray(jax.random.randint(
                jax.random.fold_in(key, 4), (n_distinct, args.prompt_len), 0,
                cfg.vocab_size))
            picks = np.random.default_rng(args.seed + 1).integers(
                0, n_distinct, n_req)
            mix = parse_mix(args.sampling_mix, base) if args.sampling_mix \
                else [base]
            sps = [mix[i % len(mix)] for i in range(n_req)]
            dkw = dict(disaggregate=disagg is not None,
                       prefill_mesh=pmesh, decode_mesh=dmesh)
            if spec is not None:
                # hardware-derived pool/slots — no manual num_pages knob
                llm = LLMEngine(model, params, backend="continuous",
                                spec=spec, speculative=spec_cfg,
                                enable_prefix_cache=args.prefix_cache,
                                **dkw)
                print(llm.deployment.describe())
                if disagg is not None:
                    print(llm._eng.prefill.deployment.describe())
                slots = llm._eng.num_slots
            else:
                slots = args.batch
                llm = LLMEngine(
                    model, params, backend="continuous", max_len=max_len,
                    num_slots=slots, page_size=args.page_size,
                    num_pages=1 + slots * -(-max_len // args.page_size) * 2,
                    prefill_chunk=args.prefill_chunk,
                    cache_dtype=cache_dtype,
                    enable_prefix_cache=args.prefix_cache, mesh=serve_mesh,
                    tp_reduce=args.tp_reduce, speculative=spec_cfg, **dkw)
            t0 = time.time()
            outs = llm.generate([pool_prompts[picks[i]] for i in range(n_req)],
                                sps, max_new_tokens=args.max_new,
                                arrival_times=arrivals)
            dt = time.time() - t0
            stats = llm.last_stats
            n_tok = sum(len(o.token_ids) for o in outs)
            print(f"arch={cfg.name} continuous slots={slots} "
                  f"requests={n_req} rate={args.arrival_rate}/s "
                  f"steps={stats.steps} occupancy={stats.occupancy:.2f} "
                  f"preemptions={stats.preemptions}")
            print("host ms by phase: " + " ".join(
                f"{name.removeprefix('engine.')}={ms:.1f}"
                for name, ms in sorted(stats.host_ms.items(),
                                       key=lambda kv: -kv[1])))
            if serve_mesh is not None:
                sp = llm.serve_plan
                print(f"mesh: data={serve_mesh.shape['data']} x "
                      f"model={serve_mesh.shape['model']} "
                      f"(reduce={sp.reduce}) — "
                      f"{llm.kv_token_bytes_per_device()} KV bytes/token "
                      f"per device, "
                      f"{sp.psum_bytes_per_step(model, slots)}"
                      f" collective bytes/step per device")
            if args.sampling_mix:
                print(f"sampling mix: {args.sampling_mix} "
                      f"(one decode-step signature, per-slot data)")
            print(f"tokens={n_tok} wall={dt:.2f}s "
                  f"({n_tok / dt:.1f} tok/s incl. compile)")
            print(f"prefill: {stats.chunks} chunks, "
                  f"{stats.prefill_tokens}/{stats.prompt_tokens} prompt "
                  f"tokens computed, prefix hit rate "
                  f"{stats.prefix_hit_rate:.2f}, cow={stats.cow_events}")
            if spec_cfg is not None:
                print(f"speculative: gamma={args.gamma} "
                      f"draft={args.spec_draft} "
                      f"windows={stats.spec_windows} "
                      f"accepted/window={stats.accepted_per_window:.2f} "
                      f"drafted={stats.spec_drafted} "
                      f"wasted={stats.spec_wasted}")
            if disagg is not None:
                print(f"handoff: {stats.handoffs} chains, "
                      f"{stats.handoff_pages} pages, "
                      f"{stats.handoff_bytes} bytes, "
                      f"{stats.handoff_shared_tokens} prefix-shared tokens")
            q = stats.ttft_quantiles()
            if q is not None:
                print(f"ttft p50={q[0] * 1e3:.1f}ms p99={q[1] * 1e3:.1f}ms")
            reasons = {}
            for o in outs:
                reasons[o.finish_reason] = reasons.get(o.finish_reason, 0) + 1
            if spec_cfg is not None:
                per_req = " ".join(
                    f"r{rid}:p{st['preemptions']}/c{st['chunks']}"
                    f"/w{st['spec_windows']}/a{st['spec_accepted']}"
                    for rid, st in sorted(stats.per_request.items()))
            else:
                per_req = " ".join(
                    f"r{rid}:p{st['preemptions']}/c{st['chunks']}"
                    for rid, st in sorted(stats.per_request.items()))
            print(f"finish reasons: {reasons}")
            print(f"per-request preemptions/chunks: {per_req}")
            print("sample:", outs[0].token_ids[:16])
            return 0

        prompts = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 1), (args.batch, args.prompt_len), 0,
            cfg.vocab_size))
        if cfg.frontend == "vision" and backend == "static":
            # vision frontends serve batch dicts (tokens + image embeds)
            # through ServeEngine directly; LLMEngine fronts token-only
            # requests
            from repro.runtime.engine import ServeEngine
            batch = {"tokens": jnp.asarray(prompts),
                     "image_embeds": jax.random.normal(
                         jax.random.fold_in(key, 2),
                         (args.batch, 8, cfg.d_model), jnp.bfloat16)}
            eng = ServeEngine(model, params, max_len=max_len + 8)
            t0 = time.time()
            out = eng.generate(batch, max_new_tokens=args.max_new,
                               sampling_params=base)
            dt = time.time() - t0
            toks = np.asarray(out.tokens)
            n_tok = toks.size
            print(f"arch={cfg.name} backend=static(vision) "
                  f"batch={args.batch} new_tokens={toks.shape[1]} "
                  f"wall={dt:.2f}s ({n_tok/dt:.1f} tok/s incl. compile)")
            print("sample:", toks[0, :16].tolist())
            return 0
        if backend == "speculative":
            import dataclasses
            draft_cfg = dataclasses.replace(
                cfg, name=cfg.name + "-draft",
                n_layers=max(2, cfg.n_layers // 4))
            draft = build_model(draft_cfg)
            draft_params = draft.init(jax.random.fold_in(key, 3))
            llm = LLMEngine(model, params, backend="speculative",
                            max_len=max_len, draft_model=draft,
                            draft_params=draft_params, gamma=4)
            t0 = time.time()
            outs = llm.generate(prompts[:1], base, max_new_tokens=args.max_new)
            dt = time.time() - t0
            m = outs[0].metrics
            print(f"speculative: accepted/window="
                  f"{m['accepted_per_window']:.2f} over {m['windows']} windows")
        else:
            llm = LLMEngine(model, params, backend="static", max_len=max_len,
                            spec=spec, cache_dtype=cache_dtype)
            if spec is not None:
                print(llm._eng.deployment.describe())
            t0 = time.time()
            outs = llm.generate(prompts, base, max_new_tokens=args.max_new)
            dt = time.time() - t0

    n_tok = sum(len(o.token_ids) for o in outs)
    print(f"arch={cfg.name} backend={backend} batch={len(outs)} "
          f"new_tokens={len(outs[0].token_ids)} "
          f"wall={dt:.2f}s ({n_tok/dt:.1f} tok/s incl. compile)")
    print("sample:", outs[0].token_ids[:16])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
