"""Serving engines: static batch and continuous batching.

``ServeEngine`` mirrors the paper's deployment model (§VI "Deployment"):
prefill and decode are separate entry points (Splitwise/Dynamo-style phase
splitting, the paper's prerequisite architecture), and the decode loop runs
as ONE jitted ``lax.scan`` over steps — no host round-trip per token, the
JAX analogue of the RPU's host-free autonomous execution ("eliminating the
host-driven offload model used by GPUs").

``ContinuousServeEngine`` is the throughput path the paper's ISO-TDP claim
rests on: decode is bandwidth-bound, so sustained tokens/s is proportional
to slot occupancy.  Requests arrive raggedly; iteration-level batching
admits each one into a freed decode slot the moment both a slot and KV
pages are available.  Admission runs **chunked prefill straight into the
page pools**: each iteration advances every admitted-but-unfilled request
by one fixed-size chunk (one jitted shape, batched across slots at ragged
offsets) interleaved with the fused decode step, so a long prompt never
stalls the running batch.  With prefix caching on, admission shares a
matching prompt's leading pages read-only and prefill starts at the first
unseen token — lower TTFT and fewer prefill FLOPs for shared-prefix
traffic.

Request-level generation API (see ``runtime.sampling``): every request
carries its own ``SamplingParams``; the batched per-slot sampler is fused
into the jitted decode step, with per-slot temperature / top-k / top-p /
min-p / seed as ``(num_slots,)`` DATA arrays — changing the request mix
never recompiles.  Stop-token and max-tokens finish reasons are applied
on-host between steps, and progress is emitted as structured
``RequestOutput`` deltas through the incremental ``add_request()`` /
``step()`` interface (or the ``run(..., on_output=)`` streaming callback).
``runtime.llm.LLMEngine`` is the one front-end over both engines plus
speculative decoding.

Both engines are mesh-agnostic: pass shardings built by ``parallel.plan``
to run the same code distributed; CPU tests run them single-device.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.model import Model
from repro.kernels.decode_attention.paged_kernel import (live_walk,
                                                        pages_per_step)
from repro.parallel import hints
from repro.quant import kv as kvq
from repro.quant.linear import quantize_params
from repro.runtime import sampling
from repro.runtime.sampling import SamplingParams
from repro.runtime.kv_cache import PagedKVCache
from repro.runtime.scheduler import HANDOFF, RUNNING, Request, Scheduler
from repro.runtime.speculative import SpeculativeConfig, _check_rewindable
from repro.runtime.state_cache import (RingPageSpace, model_cache_layout,
                                       ring_pages_needed)
from repro.runtime.tracing import StepLog, phase_ms, span


@dataclasses.dataclass
class RequestOutput:
    """One structured progress/result record for a request.

    Streaming emits one per request per engine iteration that produced
    tokens (``new_token_ids`` is the delta — across a preemption-restart
    the re-derived tokens are NOT re-emitted); the final record has
    ``finished=True`` with a ``finish_reason`` of "stop" or "length".
    The cumulative fields (``token_ids``, ``logprobs``) are populated on
    finished records only — intermediate deltas leave them empty so the
    host loop stays O(tokens), not O(tokens^2), per request.  Contract
    across backends: concatenating ``new_token_ids`` over every emitted
    record yields the full stream (static/speculative emit one record
    carrying everything; continuous spreads it over deltas), and the
    finished record's ``token_ids`` always holds the complete result —
    one-shot callers read ``token_ids``, streaming callers accumulate
    ``new_token_ids``."""
    rid: int
    new_token_ids: list[int]
    token_ids: list[int]               # cumulative; finished records only
    finished: bool = False
    finish_reason: str | None = None
    logprobs: list[float] | None = None    # cumulative, iff requested
    prompt_logprobs: list[float] | None = None   # finished records, iff asked
    metrics: dict = dataclasses.field(default_factory=dict)


def _seed_from_key(key) -> int:
    """Legacy ``key=`` arguments map onto the seeded-stream scheme."""
    return int(np.asarray(jax.random.key_data(key)).ravel()[-1]) & 0x7FFFFFFF


@dataclasses.dataclass
class GenerationResult:
    tokens: jnp.ndarray          # (B, n_new) int32
    logprobs: jnp.ndarray | None
    steps: int


class ServeEngine:
    """Batched request serving for one model (static batch)."""

    def __init__(self, model: Model, params: Any, *, max_len: int | None = None,
                 spec=None, sampling_params: SamplingParams | None = None,
                 donate_cache: bool = True, cache_dtype=None,
                 weight_format: str | None = None,
                 max_top_k: int = sampling.MAX_TOP_K):
        self.model = model
        self.params = params
        self.deployment = None
        if spec is not None:        # DeploymentSpec (runtime.deployment)
            dep = spec.resolve(model, params=params)
            self.deployment = dep
            max_len = dep.max_len if max_len is None else max_len
            cache_dtype = dep.cache_dtype if cache_dtype is None \
                else cache_dtype
            weight_format = spec.weight_format if weight_format is None \
                else weight_format
        if max_len is None:
            raise ValueError("pass max_len= or a DeploymentSpec via spec=")
        if kvq.is_quantized_cache_dtype(cache_dtype):
            raise NotImplementedError(
                "quantized cache_dtype (fp8/int8) needs the paged pools of "
                "the continuous engine; the static engine's dense cache "
                "stays a plain dtype")
        self.weight_format = weight_format
        if weight_format is not None:
            self.params = quantize_params(self.params, weight_format)
        self.max_len = max_len
        self.default_sampling = sampling_params or sampling.GREEDY
        self.max_top_k = int(max_top_k)
        self.cache_dtype = cache_dtype
        self._decode_loop = jax.jit(
            self._decode_loop_impl,
            static_argnames=("n_steps",),
            donate_argnums=(1,) if donate_cache else (),
        )
        self._prefill = jax.jit(self.model.prefill)

    # -- phase 1: prefill ---------------------------------------------------
    def prefill(self, batch: dict):
        """Run the prompt; returns (first_token_logits, cache, prompt_len)."""
        b = (batch["features"] if "features" in batch else batch["tokens"]).shape[0]
        cache = self.model.init_cache(b, self.max_len, dtype=self.cache_dtype)
        logits, cache = self._prefill(self.params, batch, cache)
        plen = batch["tokens"].shape[1]
        if "image_embeds" in batch:
            plen += batch["image_embeds"].shape[1]
        return logits, cache, plen

    # -- phase 2: autonomous decode loop -------------------------------------
    def _decode_loop_impl(self, first_tokens, cache, start_pos, temp, topk,
                          topp, minp, seed, rep, bias_ids, bias_vals,
                          presence, *, n_steps: int):
        rows = jnp.arange(first_tokens.shape[0])

        def step(carry, _):
            tokens, cache, pos, pres = carry
            # the incoming token joins the stream before the next draw —
            # the repetition penalty sees prompt + every generated token
            pres = pres.at[rows, tokens].set(True)
            logits, cache = self.model.decode_step(self.params, tokens, cache,
                                                   pos)
            # the token being generated sits at sequence index pos + 1
            nxt, lp = sampling.sample_slots(
                logits, temp, topk, topp, minp, seed, pos + 1,
                max_top_k=self.max_top_k, rep_penalty=rep,
                bias_ids=bias_ids, bias_vals=bias_vals, presence=pres)
            return (nxt, cache, pos + 1, pres), (nxt, lp)

        (_, cache, _, _), (toks, lps) = jax.lax.scan(
            step, (first_tokens, cache, start_pos, presence), length=n_steps)
        return jnp.moveaxis(toks, 0, 1), jnp.moveaxis(lps, 0, 1), cache

    def _resolve_params(self, b: int, sampling_params, key) -> list[SamplingParams]:
        if sampling_params is None:
            sp = self.default_sampling
            if key is not None and not sp.is_greedy and sp.seed == 0:
                sp = dataclasses.replace(sp, seed=_seed_from_key(key))
            sps = [sp] * b
        elif isinstance(sampling_params, SamplingParams):
            sps = [sampling_params] * b
        else:
            sps = list(sampling_params)
            if len(sps) != b:
                raise ValueError(f"{len(sps)} SamplingParams for batch {b}")
        for sp in sps:
            if sp.top_k > self.max_top_k:
                raise ValueError(f"top_k={sp.top_k} exceeds the engine's "
                                 f"static max_top_k={self.max_top_k}")
        return sps

    def generate(self, batch: dict, *, max_new_tokens: int,
                 sampling_params=None, key=None) -> GenerationResult:
        """prefill + decode max_new_tokens; returns all generated tokens.

        ``sampling_params``: one ``SamplingParams`` for the whole batch or a
        per-row list — data, not shapes, so any mix shares the compiled
        loop.  Stop-token truncation is the caller's concern (the scan has
        a fixed trip count); ``LLMEngine`` applies it."""
        b = (batch["features"] if "features" in batch
             else batch["tokens"]).shape[0]
        sps = self._resolve_params(b, sampling_params, key)
        temp, topk, topp, minp, seed = (
            jnp.asarray(a) for a in sampling.stack_params(sps))
        rep, bias_ids, bias_vals = (
            jnp.asarray(a) for a in sampling.stack_extras(sps))
        # token-presence rows seed the repetition penalty with the prompt
        pres0 = np.zeros((b, self.model.cfg.padded_vocab), np.bool_)
        if "tokens" in batch:
            pres0[np.arange(b)[:, None], np.asarray(batch["tokens"])] = True
        pres0 = jnp.asarray(pres0)
        logits, cache, plen = self.prefill(batch)
        first, lp0 = sampling.sample_slots(
            logits, temp, topk, topp, minp, seed,
            jnp.full((b,), plen, jnp.int32), max_top_k=self.max_top_k,
            rep_penalty=rep, bias_ids=bias_ids, bias_vals=bias_vals,
            presence=pres0)
        toks, lps, cache = self._decode_loop(
            first, cache, jnp.int32(plen), temp, topk, topp, minp, seed,
            rep, bias_ids, bias_vals, pres0,
            n_steps=max_new_tokens - 1)
        all_toks = jnp.concatenate([first[:, None], toks], axis=1)
        all_lps = (jnp.concatenate([lp0[:, None], lps], axis=1)
                   if any(sp.logprobs for sp in sps) else None)
        return GenerationResult(tokens=all_toks, logprobs=all_lps,
                                steps=max_new_tokens)


@dataclasses.dataclass
class ContinuousStats:
    """Outcome of one ``ContinuousServeEngine.run``."""
    results: dict                 # rid -> np.ndarray (n_new,) int32
    steps: int                    # fused decode iterations executed
    occupancy: float              # mean fraction of decoding slots per step
    wall: float                   # seconds, admission of first request -> done
    preemptions: int
    chunks: int = 0               # prefill chunk rows executed
    prefill_tokens: int = 0       # prompt tokens actually computed
    prompt_tokens: int = 0        # prompt tokens across all admissions
    prefix_hit_tokens: int = 0    # prompt tokens served from shared pages
    cow_events: int = 0
    host_ms: dict = dataclasses.field(default_factory=dict)
    # host_ms[span name] = milliseconds of host self time in that phase of
    # step() over the run (``runtime.tracing``)
    # -- speculative decoding (all zero when speculation is off) --
    spec_windows: int = 0         # draft/verify windows across all requests
    spec_drafted: int = 0         # draft proposals made (gamma per window)
    spec_accepted: int = 0        # draft proposals accepted
    # -- disaggregated serving (all zero on a colocated engine) --
    handoffs: int = 0             # chains transferred prefill -> decode
    handoff_pages: int = 0        # pages physically moved
    handoff_bytes: int = 0        # pool bytes moved (all leaves, both sets)
    handoff_shared_tokens: int = 0  # transfer skipped via decode-side prefix
    per_request: dict = dataclasses.field(default_factory=dict)
    # per_request[rid] = {"preemptions", "chunks", "shared_tokens", "ttft",
    #                     "tpot", "finish_time", "spec_windows",
    #                     "spec_accepted"}
    outputs: dict = dataclasses.field(default_factory=dict)
    # outputs[rid] = final RequestOutput (finish_reason, logprobs, timing)

    @property
    def total_tokens(self) -> int:
        return int(sum(t.shape[0] for t in self.results.values()))

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hit_tokens / max(self.prompt_tokens, 1)

    @property
    def accepted_per_window(self) -> float:
        """Mean draft proposals accepted per window (0..gamma); each window
        also emits one corrected/bonus token on top."""
        return self.spec_accepted / max(self.spec_windows, 1)

    @property
    def spec_wasted(self) -> int:
        """Draft tokens proposed but rejected — the speculation overhead."""
        return self.spec_drafted - self.spec_accepted

    def latency_quantiles(self, metric: str = "ttft") -> dict | None:
        """p50/p95/p99/mean of a per-request latency metric, or None.

        metric is a key of the per_request records — "ttft" (arrival ->
        first token) or "tpot" (mean inter-token seconds after the first).
        Requests where the metric is unset (e.g. single-token outputs have
        no TPOT) are skipped.
        """
        ts = sorted(r[metric] for r in self.per_request.values()
                    if r.get(metric) is not None)
        if not ts:
            return None
        def pct(q: float) -> float:
            return ts[min(len(ts) - 1, int(len(ts) * q))]
        return {"p50": pct(0.50), "p95": pct(0.95), "p99": pct(0.99),
                "mean": sum(ts) / len(ts)}

    def ttft_quantiles(self) -> tuple[float, float, float] | None:
        """(p50, p99, mean) time-to-first-token in seconds, or None."""
        q = self.latency_quantiles("ttft")
        if q is None:
            return None
        return q["p50"], q["p99"], q["mean"]


class ContinuousServeEngine:
    """Iteration-level continuous batching over a block-paged KV cache.

    The jitted decode step has a fixed slot batch; per-slot page tables and
    ragged positions route each slot's K/V stream through the physical page
    pools (``Model.decode_step_paged`` — on accelerators the gather-fused
    Pallas kernel, no dense intermediate), and the batched per-slot sampler
    draws each slot's next token inside the same jitted step.  Admission
    (chunked prefill into the pools via ``Model.prefill_chunk_paged``),
    growth, eviction, copy-on-write, finish-reason checks, and output
    emission are host-side bookkeeping between steps — no recompiles: the
    only jitted shapes are the decode step and one ``(bucket,
    prefill_chunk)`` prefill chunk per power-of-two bucket, and every
    sampling control is data.

    Drive it incrementally (``add_request`` then ``step`` until
    ``has_unfinished()`` is False, collecting ``RequestOutput`` deltas) or
    in batch via ``run(requests, on_output=...)``.

    Sizing: pass a ``DeploymentSpec`` via ``spec=`` and the pool/slot
    knobs (``num_pages``/``num_slots``/``page_size``/``max_len``/
    ``prefill_chunk``/``cache_dtype``/``mesh`` and the scheduler's
    ``max_decode_slots`` admission hint) derive from the hardware point's
    memory budget and bandwidth roofline (``runtime.deployment``);
    explicit kwargs override individual values.  The resolved budget is
    kept on ``self.deployment``.
    """

    def __init__(self, model: Model, params: Any, *,
                 num_slots: int | None = None, page_size: int | None = None,
                 num_pages: int | None = None, max_len: int | None = None,
                 spec=None,
                 sampling_params: SamplingParams | None = None,
                 cache_dtype=None, weight_format: str | None = None,
                 prefill_chunk: int | None = None,
                 enable_prefix_cache: bool = True,
                 max_top_k: int = sampling.MAX_TOP_K,
                 mesh=None, tp_reduce: str = "auto",
                 max_decode_slots: int | None = None,
                 speculative: SpeculativeConfig | None = None,
                 phase: str = "colocated"):
        if model.cfg.frontend is not None:
            raise NotImplementedError(
                "continuous batching serves token frontends only")
        if phase not in ("colocated", "prefill", "decode"):
            raise ValueError(f"phase={phase!r}: expected 'colocated', "
                             f"'prefill', or 'decode'")
        self.phase = phase
        self.model = model
        self.params = params
        # -- DeploymentSpec resolution: pool/slot knobs derived from the
        # hardware point; explicit kwargs override individual values --
        self.deployment = None
        if spec is not None:
            rkw = {}
            if speculative is not None:
                # price the draft into the budget: weights join the
                # capacity split, and every logical KV page carries both
                # pool sets' bytes (self-draft duplicates the target's)
                rkw = dict(draft=speculative.draft_model or model,
                           draft_params=(speculative.draft_params
                                         if speculative.draft_model
                                         is not None else params),
                           gamma=speculative.gamma)
            dep = spec.resolve(model, params=params, mesh=mesh, phase=phase,
                               **rkw)
            self.deployment = dep
            mesh = dep.mesh
            num_slots = dep.num_slots if num_slots is None else num_slots
            page_size = dep.page_size if page_size is None else page_size
            num_pages = dep.num_pages if num_pages is None else num_pages
            max_len = dep.max_len if max_len is None else max_len
            prefill_chunk = dep.prefill_chunk if prefill_chunk is None \
                else prefill_chunk
            cache_dtype = dep.cache_dtype if cache_dtype is None \
                else cache_dtype
            weight_format = spec.weight_format if weight_format is None \
                else weight_format
            max_decode_slots = dep.max_decode_slots \
                if max_decode_slots is None else max_decode_slots
            if tp_reduce == "auto":
                tp_reduce = dep.tp_reduce
        missing = [k for k, v in (("num_slots", num_slots),
                                  ("page_size", page_size),
                                  ("num_pages", num_pages),
                                  ("max_len", max_len)) if v is None]
        if missing:
            raise ValueError(
                f"pass a DeploymentSpec via spec= or the explicit knobs "
                f"{missing}")
        prefill_chunk = 64 if prefill_chunk is None else prefill_chunk
        self.num_slots = num_slots
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_len = max_len
        self.max_decode_slots = max_decode_slots
        self.max_blocks = -(-max_len // page_size)
        if num_pages - 1 < self.max_blocks:   # page 0 is scratch
            raise ValueError(
                f"num_pages={num_pages} cannot back even one max-length "
                f"request ({self.max_blocks} blocks + scratch)")
        self.default_sampling = sampling_params or sampling.GREEDY
        self.max_top_k = int(max_top_k)
        kvq.validate_cache_dtype(cache_dtype)
        self.cache_dtype = cache_dtype
        self.weight_format = weight_format
        if int(prefill_chunk) < 1:
            raise ValueError(f"prefill_chunk={prefill_chunk} must be >= 1")
        self.prefill_chunk = int(prefill_chunk)
        self.enable_prefix_cache = enable_prefix_cache
        self.defrag_every = 0
        self._vocab = model.cfg.padded_vocab
        # -- stateful cache layouts (runtime.state_cache): SSM/hybrid state
        # pools and ring-page reclamation for sliding-window layers --
        lay = model_cache_layout(model.plan)
        self._layout = lay
        if not lay.has_full:
            # no full-KV segment -> no shareable, CoW-protected chains;
            # the prefix index must never hand out ring or state "hits"
            self.enable_prefix_cache = False
        if lay.stateful:
            arch = model.cfg.name
            if speculative is not None:
                raise NotImplementedError(
                    f"speculative decoding is unsupported for {arch!r}: "
                    f"draft/verify rewinds token-indexed KV pages, but "
                    f"recurrent SSM state and reclaimed ring pages cannot "
                    f"rewind (recorded follow-on)")
            if phase != "colocated":
                raise NotImplementedError(
                    f"disaggregated serving is unsupported for {arch!r}: "
                    f"the KV handoff moves page chains only — recurrent "
                    f"state and ring residency need their own transfer "
                    f"(recorded follow-on)")
        if lay.has_state:
            if kvq.is_quantized_cache_dtype(cache_dtype):
                raise NotImplementedError(
                    f"cache_dtype={cache_dtype!r} is unsupported for the "
                    f"state-carrying arch {model.cfg.name!r}: SSM state "
                    f"pools stay bf16/f32 — quantized state is a recorded "
                    f"follow-on")
            if mesh is not None:
                raise NotImplementedError(
                    f"tensor-parallel serving of the state-carrying arch "
                    f"{model.cfg.name!r} needs sharded state pools "
                    f"(recorded follow-on); run it single-device")
        self.ring_pages = 0
        if lay.has_ring:
            # size the ring pool so ensure() can never fail: every slot at
            # its transient (mid-prefill-chunk) residency peak at once
            self.ring_pages = ring_pages_needed(
                num_slots=num_slots, window=lay.ring_window,
                page_size=page_size, max_blocks=self.max_blocks,
                prefill_chunk=self.prefill_chunk)
        # -- mesh execution (tensor-parallel paged serving) --
        self.mesh = mesh
        self.serve_plan = None
        self._pool_model = model
        if mesh is not None:
            from repro.parallel.plan import make_paged_serve_plan
            self.serve_plan = make_paged_serve_plan(model.cfg, mesh,
                                                    reduce=tp_reduce)
            self._local_model = Model(
                self.serve_plan.local_config(model.cfg),
                moe_impl=model.moe_impl)
            if self.serve_plan.kv_repl > 1:
                # kvh < tp: KV projections physically replicate per head
                # group, and the pools widen to tp KV heads (one per shard)
                params = self.serve_plan.prepare_params(params, model.cfg)
                self._pool_model = Model(
                    self.serve_plan.pool_config(model.cfg),
                    moe_impl=model.moe_impl)
            if weight_format is not None:
                # pack AFTER the kv_repl expansion (packing operates on the
                # physical column layout each shard slices) and BEFORE
                # device_put, so codes/scales shard through the same
                # partition specs as the weights they replace
                params = quantize_params(params, weight_format)
            self.params = jax.device_put(
                params, self.serve_plan.param_shardings(params))
            self._param_specs = self.serve_plan.param_specs(params)
            self._pool_specs = self.serve_plan.pool_specs(
                self._pool_model, cache_dtype=self.cache_dtype)
            self._paged_decode = self._shard_paged(
                self._local_model.decode_step_paged, n_extra=1)   # pos
            self._paged_chunk = self._shard_paged(
                self._local_model.prefill_chunk_paged, n_extra=2)  # start, valid
            self._paged_chunk_scored = self._shard_paged(
                self._local_model.prefill_chunk_scored_paged, n_extra=2,
                n_out=2)
        else:
            if weight_format is not None:
                self.params = quantize_params(params, weight_format)
            self._paged_decode = model.decode_step_paged
            self._paged_chunk = model.prefill_chunk_paged
            self._paged_chunk_scored = model.prefill_chunk_scored_paged
        # ring/state entry points: same model fns with the extra operands
        # threaded (ring tables are replicated data like page tables, so
        # the TP path wraps them as plain extras; state pools are
        # single-device only — guarded above)
        if lay.has_ring:
            if mesh is not None:
                lm = self._local_model
                self._paged_decode_ring = self._shard_paged(
                    lambda p, t, pl, tab, pos, ring:
                        lm.decode_step_paged(p, t, pl, tab, pos,
                                             ring_table=ring),
                    n_extra=2)
                self._paged_chunk_ring = self._shard_paged(
                    lambda p, t, pl, tab, s, v, ring:
                        lm.prefill_chunk_paged(p, t, pl, tab, s, v,
                                               ring_table=ring),
                    n_extra=3)
                self._paged_chunk_scored_ring = self._shard_paged(
                    lambda p, t, pl, tab, s, v, ring:
                        lm.prefill_chunk_scored_paged(p, t, pl, tab, s, v,
                                                      ring_table=ring),
                    n_extra=3, n_out=2)
            else:
                self._paged_decode_ring = (
                    lambda p, t, pl, tab, pos, ring:
                        model.decode_step_paged(p, t, pl, tab, pos,
                                                ring_table=ring))
                self._paged_chunk_ring = (
                    lambda p, t, pl, tab, s, v, ring:
                        model.prefill_chunk_paged(p, t, pl, tab, s, v,
                                                  ring_table=ring))
                self._paged_chunk_scored_ring = (
                    lambda p, t, pl, tab, s, v, ring:
                        model.prefill_chunk_scored_paged(p, t, pl, tab, s, v,
                                                         ring_table=ring))
        if lay.has_state:
            self._paged_decode_state = (
                lambda p, t, pl, tab, pos, st, ring, ok:
                    model.decode_step_paged(p, t, pl, tab, pos, states=st,
                                            ring_table=ring, state_ok=ok))
            self._paged_chunk_state = (
                lambda p, t, pl, tab, s, v, st, ring, sl:
                    model.prefill_chunk_paged(p, t, pl, tab, s, v, states=st,
                                              ring_table=ring, slot_idx=sl))
            self._paged_chunk_scored_state = (
                lambda p, t, pl, tab, s, v, st, ring, sl:
                    model.prefill_chunk_scored_paged(
                        p, t, pl, tab, s, v, states=st, ring_table=ring,
                        slot_idx=sl))
        # -- speculative decoding: per-slot draft state is a SECOND set of
        # pool leaves over the SAME logical page-id space (one allocator,
        # one set of page tables), so prefix sharing, copy-on-write,
        # preemption, and defrag act on target and draft in lockstep --
        self.spec = speculative
        self._gamma = int(speculative.gamma) if speculative is not None else 0
        self._draft_plan = None
        if speculative is not None:
            _check_rewindable(model)
            dm = speculative.draft_model
            if dm is None:
                # self-draft: same weights propose and verify (acceptance
                # ~1; tests and smoke runs).  The draft still keeps its own
                # pool leaves — its scan-ahead KV writes must not clobber
                # the target's verified entries.
                self._draft_params = self.params
                self._draft_pool_model = self._pool_model
                self._draft_plan = self.serve_plan
                self._paged_draft_decode = self._paged_decode
                self._paged_draft_chunk = self._paged_chunk
            else:
                if dm.cfg.padded_vocab != model.cfg.padded_vocab:
                    raise ValueError(
                        "draft and target must share a vocabulary: "
                        f"{dm.cfg.padded_vocab} vs {model.cfg.padded_vocab}")
                dparams = speculative.draft_params
                if dparams is None:
                    raise ValueError("SpeculativeConfig.draft_params is "
                                     "required when draft_model is set")
                self._draft_pool_model = dm
                if mesh is not None:
                    from repro.parallel.plan import make_paged_serve_plan
                    self._draft_plan = make_paged_serve_plan(
                        dm.cfg, mesh, reduce=tp_reduce)
                    dlocal = Model(self._draft_plan.local_config(dm.cfg),
                                   moe_impl=dm.moe_impl)
                    if self._draft_plan.kv_repl > 1:
                        dparams = self._draft_plan.prepare_params(dparams,
                                                                  dm.cfg)
                        self._draft_pool_model = Model(
                            self._draft_plan.pool_config(dm.cfg),
                            moe_impl=dm.moe_impl)
                    if weight_format is not None:
                        dparams = quantize_params(dparams, weight_format)
                    self._draft_params = jax.device_put(
                        dparams, self._draft_plan.param_shardings(dparams))
                    dspecs = self._draft_plan.param_specs(dparams)
                    dpool = self._draft_plan.pool_specs(
                        self._draft_pool_model, cache_dtype=self.cache_dtype)
                    self._paged_draft_decode = self._shard_paged(
                        dlocal.decode_step_paged, n_extra=1,
                        plan=self._draft_plan, param_specs=dspecs,
                        pool_specs=dpool)
                    self._paged_draft_chunk = self._shard_paged(
                        dlocal.prefill_chunk_paged, n_extra=2,
                        plan=self._draft_plan, param_specs=dspecs,
                        pool_specs=dpool)
                else:
                    if weight_format is not None:
                        dparams = quantize_params(dparams, weight_format)
                    self._draft_params = dparams
                    self._paged_draft_decode = dm.decode_step_paged
                    self._paged_draft_chunk = dm.prefill_chunk_paged
            # multi-token verify runs through the TARGET's paged decode
            # path with q_len = gamma + 1 (same dispatch, not a new kernel)
            self._paged_multi = (
                self._shard_paged(self._local_model.decode_step_paged,
                                  n_extra=2)                 # pos, valid
                if mesh is not None else model.decode_step_paged)
            self._spec_draft = jax.jit(self._spec_draft_impl,
                                       donate_argnums=(1,))
            self._spec_verify = jax.jit(self._spec_verify_impl,
                                        donate_argnums=(1, 2))
            self._draft_chunk = jax.jit(self._draft_chunk_impl,
                                        donate_argnums=(1,))
            self._copy_page_draft = jax.jit(
                functools.partial(self._copy_page_impl,
                                  self._draft_pool_model.plan),
                donate_argnums=(0,))
        # the pools are built where they live: per-shard pools (each device
        # holds its model-axis slice of every physical page, one shared
        # logical page-id space) never pass through one device whole
        self._init_pools = jax.jit(
            functools.partial(
                self._pool_model.init_paged_cache, num_pages, page_size,
                dtype=self.cache_dtype,
                ring_pages=self.ring_pages if lay.has_ring else None),
            out_shardings=None if self.serve_plan is None
            else self.serve_plan.pool_shardings(self._pool_model,
                                                cache_dtype=self.cache_dtype))
        self._step_fn = jax.jit(self._step_impl, donate_argnums=(1, 2, 3))
        self._chunk = jax.jit(self._chunk_impl, donate_argnums=(1, 2))
        self._chunk_scored = jax.jit(self._chunk_scored_impl,
                                     donate_argnums=(1, 2))
        self._copy_page = jax.jit(
            functools.partial(self._copy_page_impl, self._pool_model.plan),
            donate_argnums=(0,))
        # KV-handoff seam: gather page rows to host / scatter staged rows
        # into the pools.  One compile per pow-2 chain-length bucket.
        self._gather_pages = jax.jit(
            functools.partial(self._gather_pages_impl, self._pool_model.plan))
        self._scatter_pages = jax.jit(
            functools.partial(self._scatter_pages_impl, self._pool_model.plan),
            donate_argnums=(0,))
        if speculative is not None:
            self._gather_pages_draft = jax.jit(functools.partial(
                self._gather_pages_impl, self._draft_pool_model.plan))
            self._scatter_pages_draft = jax.jit(functools.partial(
                self._scatter_pages_impl, self._draft_pool_model.plan),
                donate_argnums=(0,))
        self._sched: Scheduler | None = None
        self._log = StepLog()

    # -- sharded execution --------------------------------------------------
    def _shard_paged(self, fn, *, n_extra: int, n_out: int = 1, plan=None,
                     param_specs=None, pool_specs=None):
        """Wrap a paged model fn (params, tokens, pools, table, *extras) ->
        (*n_out replicated outputs, pools) in one manual shard_map over the
        serve plan's TP axis: params/pools enter pre-sliced per their
        specs, the body runs the LOCAL-geometry model (its ``tp_psum``
        marks close each column/row pair), and logits come back
        replicated.  The region is manual over EVERY mesh axis — the specs
        name only the TP axis, so the others just replicate — because XLA
        cannot partition a Pallas (Mosaic) call over an axis left
        automatic.  Page tables, positions, and every sampling tensor
        stay replicated data, so the jit signature is identical to the
        single-device path — no extra compiles per mesh shape.  The
        speculative draft model passes its own plan/specs; the target's
        are the default."""
        sp = plan if plan is not None else self.serve_plan
        param_specs = self._param_specs if param_specs is None else param_specs
        pool_specs = self._pool_specs if pool_specs is None else pool_specs

        def body(params, tokens, pools, table, *extras):
            with hints.suspend_hints(), hints.manual_tp_axis(sp.axis,
                                                             sp.reduce):
                return fn(params, tokens, pools, table, *extras)

        rep = P()
        return shard_map(
            body, mesh=sp.mesh,
            in_specs=(param_specs, rep, pool_specs, rep)
            + (rep,) * n_extra,
            out_specs=(rep,) * n_out + (pool_specs,), check_vma=False)

    # -- jitted pieces ------------------------------------------------------
    def _step_impl(self, params, pools, states, presence, tokens, pos,
                   page_table, ring_table, state_ok, temp, topk, topp, minp,
                   seed, rep, bias_ids, bias_vals):
        lay = self._layout
        hits = None          # (MoE layers, B, held experts) of a share
        if lay.has_state:
            logits, pools, states = self._paged_decode_state(
                params, tokens, pools, page_table, pos, states, ring_table,
                state_ok)
        elif lay.has_ring:
            logits, pools = self._paged_decode_ring(
                params, tokens, pools, page_table, pos, ring_table)
        elif self.mesh is None:
            logits, pools, hits = self._paged_decode(
                params, tokens, pools, page_table, pos, expert_hits=True)
        else:
            logits, pools = self._paged_decode(params, tokens, pools,
                                               page_table, pos)
        # the incoming token sits at index pos; the one being generated at
        # pos + 1 — its PRNG key is fold_in(seed, pos + 1)
        nxt, lp = sampling.sample_slots(logits, temp, topk, topp, minp, seed,
                                        pos + 1, max_top_k=self.max_top_k,
                                        rep_penalty=rep, bias_ids=bias_ids,
                                        bias_vals=bias_vals,
                                        presence=presence)
        # the sampled token joins its slot's presence row for the next
        # step's repetition penalty (rows of inactive slots accumulate
        # garbage harmlessly — admission re-uploads the host mirror)
        presence = presence.at[jnp.arange(nxt.shape[0]), nxt].set(True)
        return nxt, lp, pools, states, presence, hits

    def _chunk_impl(self, params, pools, states, presence, tokens, page_table,
                    ring_table, slot_idx, start, valid, temp, topk, topp,
                    minp, seed, rep, bias_ids, bias_vals):
        lay = self._layout
        if lay.has_state:
            logits, pools, states = self._paged_chunk_state(
                params, tokens, pools, page_table, start, valid, states,
                ring_table, slot_idx)
        elif lay.has_ring:
            logits, pools = self._paged_chunk_ring(
                params, tokens, pools, page_table, start, valid, ring_table)
        else:
            logits, pools = self._paged_chunk(
                params, tokens, pools, page_table, start, valid)
        # a request's first token is generated at index prompt_len ==
        # start + valid of its final chunk (other rows' draws are ignored);
        # presence rows carry the slot's full prompt already
        first, lp = sampling.sample_slots(logits, temp, topk, topp, minp,
                                          seed, start + valid,
                                          max_top_k=self.max_top_k,
                                          rep_penalty=rep, bias_ids=bias_ids,
                                          bias_vals=bias_vals,
                                          presence=presence)
        return first, lp, pools, states

    def _chunk_scored_impl(self, params, pools, states, presence, tokens,
                           page_table, ring_table, slot_idx, start, valid,
                           tgt, temp, topk, topp, minp, seed, rep, bias_ids,
                           bias_vals):
        """The prompt-logprobs variant of ``_chunk_impl``: the chunk's full
        (B, C, V) logits additionally score the NEXT prompt token at every
        chunk position (``tgt[i, j] = prompt[start + j + 1]``, host-built).
        The first-token draw still goes through the last-position head
        logits, so scored admissions sample the identical first token."""
        lay = self._layout
        if lay.has_state:
            last_logits, full, pools, states = self._paged_chunk_scored_state(
                params, tokens, pools, page_table, start, valid, states,
                ring_table, slot_idx)
        elif lay.has_ring:
            last_logits, full, pools = self._paged_chunk_scored_ring(
                params, tokens, pools, page_table, start, valid, ring_table)
        else:
            last_logits, full, pools = self._paged_chunk_scored(
                params, tokens, pools, page_table, start, valid)
        first, lp = sampling.sample_slots(last_logits, temp, topk, topp, minp,
                                          seed, start + valid,
                                          max_top_k=self.max_top_k,
                                          rep_penalty=rep, bias_ids=bias_ids,
                                          bias_vals=bias_vals,
                                          presence=presence)
        lf = full.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        plp = jnp.take_along_axis(lf, tgt[..., None], axis=-1)[..., 0] - lse
        return first, lp, plp, pools, states

    def _draft_chunk_impl(self, dparams, dpools, tokens, page_table, start,
                          valid):
        """Mirror one prefill chunk into the draft pools (logits dropped):
        after admission both pool sets hold the prompt's KV, so the first
        draft window attends over the full history."""
        _, dpools = self._paged_draft_chunk(dparams, tokens, dpools,
                                            page_table, start, valid)
        return dpools

    def _spec_draft_impl(self, dparams, dpools, presence, tokens, pos,
                         page_table, temp, topk, topp, minp, seed, rep,
                         bias_ids, bias_vals):
        """One draft pass: gamma chained single-token decode steps through
        the draft pools, each drawing its proposal from the SAME
        processed/filtered distribution the target verifies against
        (recorded as q), from the request's tagged TAG_PROPOSE stream —
        window randomness is keyed by absolute token index, so a
        preemption restart replays identical windows.

        The trailing KV-only step backfills the draft cache for the last
        proposal (position pos + gamma): on a full accept the next
        window's draft must see the whole history or it attends over a
        hole and diverges from the target even when the models are
        identical.  Presence mutations stay draft-local (the carry is
        dropped): proposals are not emissions until the verify step
        accepts them."""
        g = self._gamma
        rows = jnp.arange(tokens.shape[0])

        def dstep(carry, j):
            tok, pools, pres = carry
            pres = pres.at[rows, tok].set(True)
            logits, pools = self._paged_draft_decode(dparams, tok, pools,
                                                     page_table, pos + j)
            lg = sampling.apply_processors(logits, rep_penalty=rep,
                                           bias_ids=bias_ids,
                                           bias_vals=bias_vals, presence=pres)
            q = sampling.slot_dist(lg, temp, topk, topp, minp,
                                   max_top_k=self.max_top_k)
            u = sampling.spec_uniform(seed, pos + j + 1, sampling.TAG_PROPOSE)
            nxt = sampling.slot_draw(q, u)
            return (nxt, pools, pres), (nxt, q)

        (last, dpools, _), (prop, q_dists) = jax.lax.scan(
            dstep, (tokens, dpools, presence), jnp.arange(g))
        _, dpools = self._paged_draft_decode(dparams, last, dpools,
                                             page_table, pos + g)
        return jnp.moveaxis(prop, 0, 1), q_dists, dpools

    def _spec_verify_impl(self, params, pools, presence, tokens, prop,
                          q_dists, pos, page_table, temp, topk, topp, minp,
                          seed, rep, bias_ids, bias_vals):
        """One verify pass: the target scores [last_emitted, prop_1..g] as
        a single multi-token paged decode (q_len = gamma + 1 through
        ``decode_step_paged``'s 2-D form — bit-identical per-position
        logits to sequential decode on CPU), then applies the stochastic
        acceptance rule of Leviathan et al. per slot:

          accept prop_j while u_j < min(1, p(prop_j) / q(prop_j)); at the
          first rejection resample from max(p - q, 0) normalized; on a
          full accept draw the bonus token from p at the extra position.

        p and q are both ``apply_processors`` + ``slot_dist`` outputs with
        the RUNNING presence threaded position by position, so acceptance
        is correct under per-slot repetition penalty / logit bias /
        filtering.  Greedy slots (temperature <= 0) get exact one-hots on
        both sides: proposals accept iff they equal the target argmax and
        the correction IS the target argmax — byte-identical to the
        non-speculative engine.  Rejected positions need no KV rollback:
        their pool writes sit at slot positions > the new ``pos`` and are
        masked (then overwritten) by the next window.

        Returns (tokens (B, gamma+1), n_emit (B,), logprobs (B, gamma+1),
        pools, presence); entries past n_emit are padding."""
        g = self._gamma
        b = tokens.shape[0]
        rows = jnp.arange(b)
        t_in = jnp.concatenate([tokens[:, None], prop], axis=1)  # (B, g+1)
        logits, pools = self._paged_multi(
            params, t_in, pools, page_table, pos,
            jnp.full((b,), g + 1, jnp.int32))

        def pstep(pres, j):
            # token j joins the stream before position j's draw — the
            # penalty sees prompt + everything emitted through pos + j
            pres = pres.at[rows, t_in[:, j]].set(True)
            lg = sampling.apply_processors(logits[:, j], rep_penalty=rep,
                                           bias_ids=bias_ids,
                                           bias_vals=bias_vals, presence=pres)
            p = sampling.slot_dist(lg, temp, topk, topp, minp,
                                   max_top_k=self.max_top_k)
            glp = jnp.max(lg, axis=-1) - jax.nn.logsumexp(lg, axis=-1)
            return pres, (p, glp)

        _, (p_dists, glps) = jax.lax.scan(pstep, presence, jnp.arange(g + 1))
        jdx = jnp.arange(g)
        p_prop = p_dists[jdx[:, None], rows[None, :], prop.T]    # (g, B)
        q_prop = q_dists[jdx[:, None], rows[None, :], prop.T]
        u = sampling.spec_uniform(seed[None, :],
                                  pos[None, :] + jdx[:, None] + 1,
                                  sampling.TAG_ACCEPT)
        accept = u < jnp.minimum(1.0, p_prop / jnp.maximum(q_prop, 1e-20))
        n_acc = jnp.where(jnp.any(~accept, axis=0),
                          jnp.argmax(~accept, axis=0), g)        # (B,)
        # correction (first rejection) / bonus (full accept) distribution
        q_pad = jnp.concatenate([q_dists, jnp.zeros_like(q_dists[:1])],
                                axis=0)
        p_at = p_dists[n_acc, rows]                              # (B, V)
        resid = jnp.maximum(p_at - q_pad[n_acc, rows], 0.0)
        rs = jnp.sum(resid, axis=-1, keepdims=True)
        corr = jnp.where((n_acc[:, None] == g) | (rs <= 1e-20), p_at,
                         resid / jnp.maximum(rs, 1e-20))
        uc = sampling.spec_uniform(seed, pos + n_acc + 1,
                                   sampling.TAG_CORRECT)
        corrected = sampling.slot_draw(corr, uc)
        jcols = jnp.arange(g + 1)
        out = jnp.where(jcols[None, :] < n_acc[:, None],
                        jnp.concatenate([prop, prop[:, :1]], axis=1), 0)
        out = jnp.where(jcols[None, :] == n_acc[:, None],
                        corrected[:, None], out)
        # logprobs under the target's filtered per-position distribution;
        # greedy rows report the exact max-logit logprob ``sample_slots``
        # would (same floats: max == top_k[0], same logsumexp)
        pd = jnp.moveaxis(p_dists, 0, 1)                         # (B, g+1, V)
        lp_dist = jnp.log(jnp.maximum(
            jnp.take_along_axis(pd, out[..., None], axis=-1)[..., 0], 1e-38))
        lp = jnp.where((temp <= 0.0)[:, None], jnp.moveaxis(glps, 0, 1),
                       lp_dist)
        # presence gains the EMITTED tokens only (rejected proposals were
        # never part of the stream); masked columns re-scatter the first
        # emitted token — a harmless duplicate
        emit_ok = jcols[None, :] <= n_acc[:, None]
        scat = jnp.where(emit_ok, out, out[:, :1])
        presence = presence.at[rows[:, None], scat].set(True)
        return out, n_acc + 1, lp, pools, presence

    @staticmethod
    def _copy_page_impl(plan, pools, dst, src):
        """pools[dst] = pools[src] on every pool leaf (copy-on-write).
        ``plan`` is bound per pool set (functools.partial): the target and
        the speculative draft pools each get a copy jit over their own
        segment layout.  Ring segments (``seg.window``) live in their own
        page-id space and are never shared, so full-space copy-on-write
        ids must not touch them; SSM segments carry empty pools and fall
        through the dict comprehension untouched."""
        new_pools = []
        for si, seg in enumerate(plan):
            if seg.window is not None:
                new_pools.append(pools[si])
                continue
            copy = ((lambda a: a.at[dst].set(a[src])) if seg.reps == 1
                    else (lambda a: a.at[:, dst].set(a[:, src])))
            new_pools.append(tuple(
                {k: copy(v) for k, v in pool.items()} for pool in pools[si]))
        return new_pools

    @staticmethod
    def _gather_pages_impl(plan, pools, ids):
        """Pull page rows ``ids`` out of every pool leaf (KV handoff read
        side).  Per-token quantization scale leaves ride in the pools, so
        they travel with the codes for free.  Ring segments are excluded
        (stateful layouts reject phase splitting at construction)."""
        out = []
        for si, seg in enumerate(plan):
            if seg.window is not None:
                out.append(tuple({} for _ in pools[si]))
                continue
            axis = 0 if seg.reps == 1 else 1
            out.append(tuple(
                {k: jnp.take(v, ids, axis=axis) for k, v in pool.items()}
                for pool in pools[si]))
        return out

    @staticmethod
    def _scatter_pages_impl(plan, pools, staged, ids):
        """Write staged page rows into pool pages ``ids`` (KV handoff write
        side; ``pools`` donated)."""
        new_pools = []
        for si, seg in enumerate(plan):
            if seg.window is not None:
                new_pools.append(pools[si])
                continue
            if seg.reps == 1:
                put = lambda a, vals: a.at[ids].set(vals)
            else:
                put = lambda a, vals: a.at[:, ids].set(vals)
            new_pools.append(tuple(
                {k: put(v, staged[si][pi][k]) for k, v in pool.items()}
                for pi, pool in enumerate(pools[si])))
        return new_pools

    @staticmethod
    def _permute_pools(plan, pools, gather):
        """Apply a defrag page permutation to every full-space pool leaf
        (defrag compacts the full allocator only; ring pages are exclusive
        and short-lived, so the ring space never fragments across owners
        in a way compaction could improve)."""
        gather = jnp.asarray(gather)
        new_pools = []
        for si, seg in enumerate(plan):
            if seg.window is not None:
                new_pools.append(pools[si])
                continue
            axis = 0 if seg.reps == 1 else 1
            new_pools.append(tuple(
                {k: jnp.take(v, gather, axis=axis) for k, v in pool.items()}
                for pool in pools[si]))
        return new_pools

    # -- serving state ------------------------------------------------------
    def reset(self) -> None:
        """Drop all serving state and start an empty session (jitted
        functions and their compile caches survive across sessions)."""
        lay = self._layout
        ring = None
        if lay.has_ring:
            ring = RingPageSpace(num_slots=self.num_slots,
                                 num_pages=self.ring_pages,
                                 page_size=self.page_size,
                                 max_blocks=self.max_blocks,
                                 window=lay.ring_window)
        self.cache = PagedKVCache(num_slots=self.num_slots,
                                  num_pages=self.num_pages,
                                  page_size=self.page_size,
                                  max_blocks=self.max_blocks,
                                  enable_prefix_cache=self.enable_prefix_cache,
                                  has_full=lay.has_full, ring=ring,
                                  recompute_shared=(lay.has_state
                                                    and lay.has_full))
        self._sched = Scheduler(self.cache, on_release=self._on_release,
                                max_running=self.max_decode_slots)
        self._slots = sampling.SlotSampling(self.num_slots)
        # token-presence rows (repetition penalty): host mirror + device
        # copy threaded through the jitted step
        self._presence_np = np.zeros((self.num_slots, self._vocab), np.bool_)
        self._presence = self._presence_to_device(self._presence_np)
        self._presence_dirty = False
        # drop the last session's pools before allocating this one's: both
        # at once would need twice the pool bytes on the device
        self._pools = self._states = self._draft_pools = None
        self._pools = self._init_pools()
        self._kv_walks = self._decode_walks()
        self._states = (self._pool_model.init_state_pools(self.num_slots)
                        if lay.has_state else None)
        if self.spec is not None:
            self._draft_pools = self._draft_pool_model.init_paged_cache(
                self.num_pages, self.page_size, dtype=self.cache_dtype)
            if self._draft_plan is not None:
                self._draft_pools = jax.device_put(
                    self._draft_pools,
                    self._draft_plan.pool_shardings(
                        self._draft_pool_model,
                        cache_dtype=self.cache_dtype))
        self._t0 = time.monotonic()
        self._steps = 0
        self._log.drain()
        self._spec_windows, self._spec_drafted, self._spec_accepted = 0, 0, 0
        self._requests: list[Request] = []
        self.defrag_every = 0      # run-scoped; run() re-applies its arg

    def _decode_walks(self) -> dict:
        """Window -> pages the paged decode kernel's walk folds at a time,
        for each window of the K/V- or latent-paged segments (one entry for
        a model whose layers share a window), from the pools as a shard
        holds them."""
        walks = {}
        for seg, kinds in zip(self._pool_model.plan, self._pools):
            for pool in kinds:
                k = pool.get("k", pool.get("latent"))
                if k is None or seg.window in walks:
                    continue
                walks[seg.window] = pages_per_step(
                    self.page_size, k.sharding.shard_shape(k.shape)[-1],
                    k.dtype.itemsize, self.max_blocks, seg.window)
        return walks

    def _presence_to_device(self, arr):
        """Host mirror -> device, placement-stable across steps: on a mesh
        the threaded presence comes back replicated over every device, so
        fresh uploads must match that sharding or the second step would
        recompile (the jit cache keys on committed shardings)."""
        if self.serve_plan is not None:
            return jax.device_put(
                arr, jax.sharding.NamedSharding(self.serve_plan.mesh, P()))
        return jnp.asarray(arr)

    def _on_release(self, slot: int) -> None:
        self._slots.clear(slot)
        self._presence_np[slot] = False
        self._presence_dirty = True

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def has_unfinished(self) -> bool:
        return self._sched is not None and self._sched.has_work()

    def kv_token_bytes_per_device(self) -> int:
        """Physical pool bytes one cached token costs per device (the
        strong-scaling observable: sharded leaves divide by TP).  Measured
        from the actual pool dtype, so quantized fp8/int8 pools report
        packed codes + scale-metadata bytes."""
        from repro.parallel.plan import paged_kv_token_bytes
        return paged_kv_token_bytes(
            self.model, tp=self.serve_plan.tp if self.serve_plan else 1,
            kv_repl=self.serve_plan.kv_repl if self.serve_plan else 1,
            cache_dtype=self.cache_dtype or jnp.bfloat16)

    def add_request(self, req: Request,
                    sampling_params: SamplingParams | None = None) -> None:
        """Submit one request; it enters the slot batch on a later
        ``step()`` once a slot and pages free up (honoring arrival_time)."""
        if self.phase == "decode":
            raise RuntimeError(
                "a decode-phase engine only accepts requests through the "
                "KV handoff; submit to the prefill engine (or the "
                "DisaggServeEngine front)")
        if self._sched is None:
            self.reset()
        if req.sampling is None:
            req.sampling = sampling_params or self.default_sampling
        if req.sampling.max_tokens is not None:
            req.max_new_tokens = min(req.max_new_tokens,
                                     req.sampling.max_tokens)
        if req.sampling.top_k > self.max_top_k:
            raise ValueError(f"request {req.rid}: top_k={req.sampling.top_k} "
                             f"exceeds the engine's static "
                             f"max_top_k={self.max_top_k}")
        # speculative windows scatter KV up to gamma positions past the
        # last emitted token, so a request needs that much page slack on
        # top of its own length
        if (req.prompt_len + req.max_new_tokens + self._gamma
                > self.max_blocks * self.page_size):
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + "
                f"{req.max_new_tokens} new tokens"
                + (f" + gamma {self._gamma}" if self._gamma else "")
                + f" exceeds max_len {self.max_blocks * self.page_size}")
        self._requests.append(req)
        self._sched.submit([req])

    # -- disaggregated handoff seam (prefill phase <-> decode phase) --------
    def handoff_ready(self) -> list[Request]:
        """Requests whose chains are complete and parked for transfer
        (prefill-phase engines only; deterministic rid order)."""
        return self._sched.handoff_ready()

    def admit_handoff(self, req: Request, now: float) -> int | None:
        """Decode-phase admission of a transferred request: binds a slot
        and allocates/shares its page chain (HANDOFF -> RUNNING).  Returns
        the shared-token count — decode-side prefix hits shrink the
        transfer — or None when no slot or pages are free (the chain stays
        parked on the prefill side: backpressure, not an error)."""
        return self._sched.admit_handoff(req, now)

    def extract_pages(self, ids: list[int]) -> tuple[list, int]:
        """Gather the bytes of pool pages ``ids`` to host staging buffers.

        Returns (staged, nbytes): a list of one numpy pool-pytree per pool
        set (target, then draft when speculative) and the exact payload
        byte count.  Page-id lists are padded to a pow-2 bucket (scratch
        page 0) for stable jit shapes; padding bytes are excluded from the
        accounting."""
        n = self._bucket(max(len(ids), 1))
        padded = np.zeros((n,), np.int32)
        padded[:len(ids)] = ids
        idx = jnp.asarray(padded)
        staged = [jax.device_get(self._gather_pages(self._pools, idx))]
        if self.spec is not None:
            staged.append(jax.device_get(
                self._gather_pages_draft(self._draft_pools, idx)))
        nbytes = sum(int(l.nbytes) for l in jax.tree_util.tree_leaves(staged))
        return staged, (nbytes * len(ids)) // n

    def install_pages(self, staged: list, ids: list[int]) -> None:
        """Scatter staged page bytes into this engine's pool pages ``ids``
        (decode-phase write side of the handoff).  The caller guarantees
        ``staged`` came from an engine with identical pool geometry and an
        id list of the same length."""
        n = self._bucket(max(len(ids), 1))
        padded = np.zeros((n,), np.int32)
        padded[:len(ids)] = ids
        idx = jnp.asarray(padded)
        self._pools = self._scatter_pages(self._pools, staged[0], idx)
        if self.spec is not None:
            self._draft_pools = self._scatter_pages_draft(
                self._draft_pools, staged[1], idx)

    def finish_handoff(self, req: Request) -> None:
        """Complete decode-side adoption once the page bytes landed: slot
        sampling state, the presence row (prompt + already-emitted tokens,
        exactly what a colocated engine holds at this point), and the
        decode-side prefix index — transferred chains keep their hashes,
        so they are shareable and CoW-protected like local ones."""
        slot = req.slot
        self._slots.set(slot, req.sampling)
        self._presence_np[slot] = False
        self._presence_np[slot][np.asarray(req.prompt)] = True
        for t in req.tokens:
            self._presence_np[slot, t] = True
        self._presence_dirty = True
        self.cache.index_prompt(slot, req.prompt)

    def release_handoff(self, slot: int) -> None:
        """Free a transferred chain's prefill-side slot (pages shared into
        the prefix index keep their refs)."""
        self._sched.release_handoff(slot)

    # -- host loop ----------------------------------------------------------
    @staticmethod
    def _bucket(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def _make_output(self, req: Request, new: list[int],
                     finished: bool) -> RequestOutput:
        metrics = {"ttft": req.ttft, "preemptions": req.preemptions,
                   "chunks": req.chunks, "shared_tokens": req.shared_tokens}
        if finished:
            metrics["finish_time"] = req.finish_time
            metrics["tpot"] = req.tpot
        if self.spec is not None:
            metrics["spec_windows"] = req.spec_windows
            metrics["spec_accepted"] = req.spec_accepted
        return RequestOutput(
            rid=req.rid, new_token_ids=list(new),
            token_ids=list(req.tokens) if finished else [],
            finished=finished,
            finish_reason=req.finish_reason if finished else None,
            logprobs=(list(req.logprobs)
                      if finished and req.sampling.logprobs else None),
            prompt_logprobs=(list(req.prompt_logprobs)
                             if finished and req.sampling.prompt_logprobs
                             else None),
            metrics=metrics)

    def _progress(self, req: Request, outs: list[RequestOutput]) -> None:
        """Apply finish reasons on-host and emit the unstreamed delta."""
        reason = req.check_finish()
        if reason is not None:
            req.finish_reason = reason
            self._sched.finish(req, self._now())
        if len(req.tokens) > req.emitted or reason is not None:
            new = req.tokens[req.emitted:]
            req.emitted = len(req.tokens)
            outs.append(self._make_output(req, new,
                                          finished=reason is not None))

    def _run_prefill_chunks(self, outs: list[RequestOutput],
                            rec) -> None:
        """Advance every PREFILL request by one chunk (one jitted call,
        batched across slots at ragged offsets).

        The chunk width is static (``prefill_chunk``) — size it to the
        workload: around the typical prompt length for low-latency
        admission, smaller to bound the per-iteration prefill slice
        interleaved with decode.  The page-table view is sliced to the
        pow-2 cover of the blocks actually resident after this chunk, so a
        short prompt's chunk never gathers (or attends over) the full
        ``max_blocks`` view; jitted shapes stay bounded by
        O(log2(num_slots) * log2(max_blocks))."""
        sched = self._sched
        pre = sched.prefilling()
        c = self.prefill_chunk
        with span("engine.prefill.prepare"):
            if self.cache.ring is not None:
                # ring pages back lazily (admission sizes the full space
                # only); grow each slot's ring to this chunk's frontier
                # BEFORE the table snapshot.  ``ring_pages_needed`` sizing
                # makes the all-or-nothing alloc infallible.
                for r in pre:
                    n = min(c, r.prompt_len - r.pos)
                    if not self.cache.ensure(r.slot, r.pos + n - 1):
                        raise RuntimeError(
                            "ring page pool exhausted during prefill — the "
                            "engine sizes it via ring_pages_needed(), so "
                            "this is an allocator invariant violation")
            bucket = self._bucket(len(pre))
            need = max(-(-(r.pos + min(c, r.prompt_len - r.pos))
                         // self.page_size) for r in pre)
            nb = min(self._bucket(need), self.max_blocks)
            tokens = np.zeros((bucket, c), np.int32)
            tables = np.zeros((bucket, nb), np.int32)  # pad rows -> scratch
            start = np.zeros((bucket,), np.int32)
            valid = np.zeros((bucket,), np.int32)
            table = self.cache.table()
            rtab = self.cache.ring_table()
            rtables = (np.zeros((bucket, nb), np.int32)
                       if rtab is not None else None)
            slots_ix = (np.zeros((bucket,), np.int32)
                        if self._layout.has_state else None)
            for i, r in enumerate(pre):
                n = min(c, r.prompt_len - r.pos)
                tokens[i, :n] = r.prompt[r.pos:r.pos + n]
                tables[i] = table[r.slot, :nb]
                start[i] = r.pos
                valid[i] = n
                if rtables is not None:
                    rtables[i] = rtab[r.slot, :nb]
                if slots_ix is not None:
                    slots_ix[i] = r.slot
            samp = sampling.stack_params([r.sampling for r in pre], bucket)
            extras = sampling.stack_extras([r.sampling for r in pre], bucket)
            pres = np.zeros((bucket, self._vocab), np.bool_)
            for i, r in enumerate(pre):
                pres[i] = self._presence_np[r.slot]
            sargs = (jnp.asarray(pres), jnp.asarray(tokens),
                     jnp.asarray(tables),
                     None if rtables is None else jnp.asarray(rtables),
                     None if slots_ix is None else jnp.asarray(slots_ix),
                     jnp.asarray(start), jnp.asarray(valid))
            pargs = (*(jnp.asarray(a) for a in samp),
                     *(jnp.asarray(a) for a in extras))
            scored = any(r.sampling.prompt_logprobs for r in pre)
            if scored:
                # tgt[i, j] = the prompt token position start+j predicts
                # (0-pad past the prompt — those scores are dropped below)
                tgt = np.zeros((bucket, c), np.int32)
                for i, r in enumerate(pre):
                    nxt = r.prompt[int(start[i]) + 1:
                                   int(start[i]) + int(valid[i]) + 1]
                    tgt[i, :len(nxt)] = nxt
                tgt = jnp.asarray(tgt)
        plp = None
        with span("engine.prefill.dispatch"):
            if scored:
                first, lp, plp, self._pools, self._states = \
                    self._chunk_scored(self.params, self._pools,
                                       self._states, *sargs, tgt, *pargs)
            else:
                first, lp, self._pools, self._states = self._chunk(
                    self.params, self._pools, self._states, *sargs, *pargs)
            if self.spec is not None:
                # the draft pools take the same chunk (same tables and
                # offsets); speculation is rejected for ring/state
                # layouts, so the ring and slot operands of sargs never
                # reach this path
                self._draft_pools = self._draft_chunk(
                    self._draft_params, self._draft_pools, sargs[1],
                    sargs[2], sargs[5], sargs[6])
        with span("engine.prefill.wait"):
            first = np.asarray(first)                  # device sync
            lp = np.asarray(lp)
            if plp is not None:
                plp = np.asarray(plp)
        rec.prefill_rows += len(pre)
        rec.prefill_tokens += int(valid.sum())
        with span("engine.prefill.commit"):
            for i, r in enumerate(pre):
                r.chunks += 1
                if plp is not None and r.sampling.prompt_logprobs:
                    # position start+j scores prompt[start+j+1]; the final
                    # chunk's last position predicts the FIRST GENERATED
                    # token, which is not a prompt logprob — drop it
                    n = int(valid[i])
                    keep = n - 1 if int(start[i]) + n == r.prompt_len else n
                    r.prompt_logprobs.extend(float(x) for x in plp[i, :keep])
                r.pos += int(valid[i])
                # the window slid past whole blocks during this chunk:
                # return their ring pages now (between dispatches, never
                # mid-graph)
                self.cache.reclaim(r.slot, r.pos)
                if r.pos == r.prompt_len:              # prefill complete
                    r.state = RUNNING
                    r.tokens.append(int(first[i]))
                    self._presence_np[r.slot, int(first[i])] = True
                    self._presence_dirty = True
                    if r.sampling.logprobs:
                        r.logprobs.append(float(lp[i]))
                    if r.first_token_time is None:
                        # a restart re-emits the tokens the client already
                        # has (seeded streams), so a preempted request
                        # keeps its original TTFT
                        r.first_token_time = self._now()
                    self.cache.index_prompt(r.slot, r.prompt)
                    self._progress(r, outs)
                    if self.phase == "prefill" and r.state == RUNNING:
                        # disaggregated: park the finished chain for
                        # transfer; the slot (and its pages) stays held
                        # until the decode engine adopts it
                        r.state = HANDOFF

    def step(self) -> list[RequestOutput]:
        """One scheduler iteration: admit arrived requests, advance every
        prefilling request by one chunk, run one fused decode step over the
        decoding slots.  Returns the ``RequestOutput`` deltas produced this
        iteration (may be empty — e.g. a chunk that completed no prompt).
        Never sleeps; with no work due yet it returns immediately.

        Each call is one ``engine.step`` span and one ``StepRecord``
        (``runtime.tracing``; drained by ``step_log()``)."""
        if self._sched is None:
            return []
        sched = self._sched
        preempted = sched.preemptions
        with self._log.step() as rec:
            outs = self._step(rec)
            rec.finished = sum(o.finished for o in outs)
            rec.preempted = sched.preemptions - preempted
            rec.pages_live = self.cache.allocator.num_live + (
                0 if self.cache.ring is None
                else self.cache.ring.allocator.num_live)
        return outs

    def step_log(self) -> list:
        """The ``StepRecord`` of every ``step()`` since the last call (at
        most ``tracing.MAX_RECORDS``, the newest), oldest first."""
        return self._log.drain()

    def _step(self, rec) -> list[RequestOutput]:
        sched = self._sched
        outs: list[RequestOutput] = []
        if self.phase != "decode":
            # a decode-phase engine admits only through admit_handoff();
            # preemption victims drain back to the prefill engine instead
            # of re-entering here
            with span("engine.admit"):
                for r in sched.admit(self._now()):
                    self._slots.set(r.slot, r.sampling)
                    self._presence_np[r.slot] = False
                    self._presence_np[r.slot][np.asarray(r.prompt)] = True
                    self._presence_dirty = True
                    rec.admitted += 1
        # -- chunked prefill, interleaved with the decode iterations --
        if sched.prefilling():
            self._run_prefill_chunks(outs, rec)
        if not sched.decoding():
            return outs
        with span("engine.decode.prepare"):
            # -- capacity + copy-on-write barrier for the decode writes; a
            # speculative window scatters KV at pos..pos+gamma, so the
            # whole window's pages are backed (and un-shared) before it
            # starts — windows never preempt or allocate midway --
            for req in sched.decoding():
                if sched.running.get(req.slot) is req:  # not yet preempted
                    upto = (req.pos + self._gamma if self.spec is not None
                            else None)
                    if sched.ensure_capacity(req, upto=upto):
                        for blk in range(req.pos // self.page_size,
                                         (req.pos + self._gamma)
                                         // self.page_size + 1):
                            moved = self.cache.cow(req.slot, blk)
                            if moved is not None:
                                rec.cow_copies += 1
                                self._pools = self._copy_page(
                                    self._pools, moved[1], moved[0])
                                if self.spec is not None:
                                    self._draft_pools = \
                                        self._copy_page_draft(
                                            self._draft_pools, moved[1],
                                            moved[0])
            decoding = sched.decoding()
            if not decoding:
                return outs
            if (self.defrag_every
                    and (self._steps + 1) % self.defrag_every == 0):
                gather = self.cache.defrag()
                if gather is not None:
                    self._pools = self._permute_pools(
                        self._pool_model.plan, self._pools, gather)
                    if self.spec is not None:
                        self._draft_pools = self._permute_pools(
                            self._draft_pool_model.plan, self._draft_pools,
                            gather)

            tokens = np.zeros((self.num_slots,), np.int32)
            pos = np.zeros((self.num_slots,), np.int32)
            # slots still prefilling (or free) must not touch live pages:
            # their rows are routed to the scratch page for this step
            step_table = np.zeros_like(self.cache.table())
            rtab = self.cache.ring_table()
            ring_step = None if rtab is None else np.zeros_like(rtab)
            state_ok = (np.zeros((self.num_slots,), np.bool_)
                        if self._layout.has_state else None)
            rows = [req.slot for req in decoding]
            for req in decoding:
                tokens[req.slot] = req.tokens[-1]
                pos[req.slot] = req.pos
            step_table[rows] = self.cache.table()[rows]
            if ring_step is not None:
                ring_step[rows] = rtab[rows]
            if state_ok is not None:
                # non-decoding slots run the step too (fixed batch) but
                # must not commit their garbage recurrent-state update
                state_ok[rows] = True
            if self._presence_dirty:   # admissions/releases since last step
                self._presence = self._presence_to_device(self._presence_np)
                self._presence_dirty = False
            args = tuple(jax.device_put((tokens, pos, step_table)))
            if self.spec is None:
                # one layer's pages the decode kernel reads (and walks, in
                # whole chunks), per window of the model's layers
                at = pos[rows]
                for window, ppb in self._kv_walks.items():
                    _, live, chunks = live_walk(at, self.page_size, window,
                                                ppb)
                    rec.kv_pages_live += int(live.sum())
                    rec.kv_pages_walked += int(chunks.sum()) * ppb
                args += (None if ring_step is None
                         else jnp.asarray(ring_step),
                         None if state_ok is None else jnp.asarray(state_ok))
            sargs = self._slots.arrays()
        rec.decode_slots = len(decoding)
        self._steps += 1
        if self.spec is not None:
            return self._spec_window(decoding, *args, sargs, outs)
        with span("engine.decode.dispatch"):
            nxt, lp, self._pools, self._states, self._presence, hits = \
                self._step_fn(self.params, self._pools, self._states,
                              self._presence, *args, *sargs)
        with span("engine.decode.wait"):
            nxt = np.asarray(nxt)                      # device sync
            lp = np.asarray(lp)
            if hits is not None:
                # the decoding slots' rows each held expert took, per layer
                h = np.asarray(hits)[:, rows]
                rec.moe_rows_held = int(h.sum())
                rec.moe_experts_active = int(h.any(axis=1).sum())
        with span("engine.decode.commit"):
            for req in decoding:
                if sched.running.get(req.slot) is not req:
                    continue
                req.tokens.append(int(nxt[req.slot]))
                # mirror the in-step presence update (device already has it)
                self._presence_np[req.slot, int(nxt[req.slot])] = True
                if req.sampling.logprobs:
                    req.logprobs.append(float(lp[req.slot]))
                req.pos += 1
                self.cache.reclaim(req.slot, req.pos)
                self._progress(req, outs)
        return outs

    def _spec_window(self, decoding, tok_j, pos_j, tab_j, sargs,
                     outs: list[RequestOutput]) -> list[RequestOutput]:
        """One draft/verify window over the decoding slots: gamma jitted
        draft steps (one scan) + one jitted multi-token verify, emitting
        1..gamma+1 tokens per slot.  Two compiled programs total — slot
        mix, gamma-window restarts after preemption, and admissions in
        between never retrace."""
        sched = self._sched
        with span("engine.decode.dispatch"):
            prop, q_dists, self._draft_pools = self._spec_draft(
                self._draft_params, self._draft_pools, self._presence,
                tok_j, pos_j, tab_j, *sargs)
            out, n_emit, lp, self._pools, self._presence = self._spec_verify(
                self.params, self._pools, self._presence, tok_j, prop,
                q_dists, pos_j, tab_j, *sargs)
        with span("engine.decode.wait"):
            out = np.asarray(out)                      # device sync
            n_emit = np.asarray(n_emit)
            lp = np.asarray(lp)
        with span("engine.decode.commit"):
            for req in decoding:
                if sched.running.get(req.slot) is not req:
                    continue
                n = int(n_emit[req.slot])
                req.spec_windows += 1
                req.spec_accepted += n - 1
                self._spec_windows += 1
                self._spec_drafted += self._gamma
                self._spec_accepted += n - 1
                took = 0
                for j in range(n):
                    t = int(out[req.slot, j])
                    req.tokens.append(t)
                    self._presence_np[req.slot, t] = True
                    if req.sampling.logprobs:
                        req.logprobs.append(float(lp[req.slot, j]))
                    took += 1
                    # stop/length can land mid-window: the tail tokens are
                    # never emitted, and the finished slot's presence row
                    # resets on release, so the device copy stays
                    # consistent
                    if req.check_finish() is not None:
                        break
                req.pos += took
                self._progress(req, outs)
        return outs

    def run(self, requests: Iterable[Request], *, key=None,
            defrag_every: int = 0,
            on_output: Callable[[RequestOutput], None] | None = None
            ) -> ContinuousStats:
        """Serve ``requests`` to completion; honors ``arrival_time``.

        ``on_output`` streams every ``RequestOutput`` delta as it is
        produced.  ``key`` is the legacy entropy argument: it only seeds
        requests that carry no ``SamplingParams`` of their own when the
        engine default is stochastic."""
        if self.phase != "colocated":
            raise RuntimeError(
                "phase-split engines are driven by DisaggServeEngine.run(), "
                "not directly")
        if self._sched is not None and self._sched.has_work():
            raise RuntimeError(
                "run() would reset the engine while incrementally-submitted "
                "requests are unfinished; drive step() to completion first")
        self.reset()
        self.defrag_every = defrag_every
        default = None
        if (key is not None and not self.default_sampling.is_greedy
                and self.default_sampling.seed == 0):
            default = dataclasses.replace(self.default_sampling,
                                          seed=_seed_from_key(key))
        requests = list(requests)
        for r in requests:
            self.add_request(r, sampling_params=default)

        sched = self._sched
        records = []
        while sched.has_work():
            if not sched.running:
                nxt_t = sched.next_arrival()
                if nxt_t is None:
                    break
                time.sleep(max(nxt_t - self._now(), 0.0))
            for o in self.step():
                if on_output is not None:
                    on_output(o)
            records += self._log.drain()

        results = {r.rid: np.asarray(r.tokens[:r.max_new_tokens], np.int32)
                   for r in requests}
        per_request = {r.rid: {"preemptions": r.preemptions,
                               "chunks": r.chunks,
                               "shared_tokens": r.shared_tokens,
                               "ttft": r.ttft,
                               "tpot": r.tpot,
                               "finish_time": r.finish_time,
                               "spec_windows": r.spec_windows,
                               "spec_accepted": r.spec_accepted}
                       for r in requests}
        outputs = {r.rid: self._make_output(r, [], finished=True)
                   for r in requests}
        return ContinuousStats(
            results=results,
            **_step_counts(records, self.num_slots),
            wall=self._now(),
            preemptions=sum(r.preemptions for r in requests),
            prompt_tokens=self.cache.lookup_tokens,
            prefix_hit_tokens=self.cache.hit_tokens,
            cow_events=self.cache.cow_events,
            spec_windows=self._spec_windows,
            spec_drafted=self._spec_drafted,
            spec_accepted=self._spec_accepted,
            per_request=per_request,
            outputs=outputs)


class KVHandoff:
    """KV-page transfer channel between a prefill-phase and a decode-phase
    engine.

    ``transfer`` moves one finished chain: admit on the decode side (slot +
    fresh/shared pages in ITS allocator's id space), gather the
    non-shared source pages to host staging, scatter them into the decode
    pools (all pool leaves — quantized-KV scale leaves and speculative
    draft pools travel with the chain), then release the prefill slot.
    Decode-side prefix hits skip the matched leading pages entirely —
    the same chained hashes index both sides, so a transferred chain lands
    in the decode prefix index and later requests with the same prefix
    transfer only their tail.  Byte accounting is exact (padding pages for
    the pow-2 jit buckets are excluded).

    Single-host staging (device -> host -> device); a multi-host transport
    and transfer/decode overlap are recorded follow-ons (ROADMAP).
    """

    def __init__(self, src: "ContinuousServeEngine",
                 dst: "ContinuousServeEngine"):
        for attr in ("page_size", "max_blocks", "cache_dtype"):
            a, b = getattr(src, attr), getattr(dst, attr)
            if a != b:
                raise ValueError(
                    f"handoff geometry mismatch: {attr}={a!r} on the "
                    f"prefill side vs {b!r} on the decode side")
        if (src.spec is None) != (dst.spec is None):
            raise ValueError(
                "speculative decoding must be on for both sides of a "
                "handoff (draft pools travel with the chain) or neither")
        src_repl = src.serve_plan.kv_repl if src.serve_plan else 1
        dst_repl = dst.serve_plan.kv_repl if dst.serve_plan else 1
        if src_repl != dst_repl:
            raise ValueError(
                f"handoff across kv_repl {src_repl} vs {dst_repl} meshes "
                f"needs a head-regrouping repack (recorded follow-on)")
        self.src = src
        self.dst = dst
        self.reset_counters()

    def reset_counters(self) -> None:
        self.transfers = 0
        self.pages_moved = 0
        self.bytes_moved = 0
        self.shared_tokens = 0
        self.deferrals = 0

    def transfer(self, req: Request, now: float) -> bool:
        """Move ``req``'s chain into the decode engine; False when the
        decode side has no capacity yet (the chain stays parked)."""
        src, dst = self.src, self.dst
        src_slot = req.slot
        src_chain = src.cache.chain(src_slot, req.prompt_len)
        shared = dst.admit_handoff(req, now)
        if shared is None:
            self.deferrals += 1
            return False
        dst_chain = dst.cache.chain(req.slot, req.prompt_len)
        skip = shared // src.page_size   # matched prefix pages: no copy
        ids_src, ids_dst = src_chain[skip:], dst_chain[skip:]
        self.shared_tokens += shared
        if ids_src:
            staged, nbytes = src.extract_pages(ids_src)
            dst.install_pages(staged, ids_dst)
            self.pages_moved += len(ids_src)
            self.bytes_moved += nbytes
        dst.finish_handoff(req)
        src.release_handoff(src_slot)
        self.transfers += 1
        return True


class DisaggServeEngine:
    """Disaggregated serving: a prefill-phase and a decode-phase
    ``ContinuousServeEngine`` joined by a :class:`KVHandoff`.

    Prompts are chunk-prefilled on the prefill engine (its own mesh or
    mesh slice, its own pool budget), then the finished page chain moves
    through the handoff into the decode engine, which runs pure fused
    decode steps — no prefill chunks stealing decode iterations, so TPOT
    is flat under prompt bursts and TTFT never queues behind a full decode
    batch (the paper's compute-bound/bandwidth-bound phase split made
    structural).  Greedy outputs are byte-identical to a colocated engine:
    seeded per-request sampling streams are keyed by absolute position,
    the transferred bytes are exact, and decode-side preemption drains
    back to the prefill engine for a seeded re-prefill restart.

    Same incremental surface as ``ContinuousServeEngine`` —
    ``add_request()`` / ``step()`` / ``run()`` — with one merged
    ``ContinuousStats`` (handoff counters filled in).
    """

    def __init__(self, model: Model, params: Any, *, spec=None,
                 prefill_mesh=None, decode_mesh=None,
                 num_slots: int | None = None, page_size: int | None = None,
                 num_pages: int | None = None, max_len: int | None = None,
                 prefill_slots: int | None = None,
                 prefill_pages: int | None = None,
                 prefill_chunk: int | None = None,
                 sampling_params: SamplingParams | None = None,
                 cache_dtype=None, weight_format: str | None = None,
                 enable_prefix_cache: bool = True,
                 max_top_k: int = sampling.MAX_TOP_K,
                 tp_reduce: str = "auto",
                 max_decode_slots: int | None = None,
                 speculative: SpeculativeConfig | None = None):
        common = dict(spec=spec, page_size=page_size, max_len=max_len,
                      sampling_params=sampling_params,
                      cache_dtype=cache_dtype, weight_format=weight_format,
                      enable_prefix_cache=enable_prefix_cache,
                      max_top_k=max_top_k, tp_reduce=tp_reduce,
                      speculative=speculative)
        # each phase resolves its own deployment budget (phase=) — the
        # prefill side may size fewer slots and pages than decode, and a
        # different mesh (TP degree) per phase is allowed as long as the
        # pool geometry matches (KVHandoff checks)
        self.prefill = ContinuousServeEngine(
            model, params, phase="prefill", mesh=prefill_mesh,
            num_slots=prefill_slots if prefill_slots is not None
            else num_slots,
            num_pages=prefill_pages if prefill_pages is not None
            else num_pages,
            prefill_chunk=prefill_chunk, **common)
        self.decode = ContinuousServeEngine(
            model, params, phase="decode", mesh=decode_mesh,
            num_slots=num_slots, num_pages=num_pages,
            max_decode_slots=max_decode_slots, **common)
        self.handoff = KVHandoff(self.prefill, self.decode)
        self.model = model
        self.default_sampling = self.decode.default_sampling
        self._requests: list[Request] = []

    # the decode side is the steady-state resident (the LLM facade's
    # introspection points: budget, plan, per-token pool bytes)
    @property
    def deployment(self):
        return self.decode.deployment

    @property
    def serve_plan(self):
        return self.decode.serve_plan

    @property
    def num_slots(self) -> int:
        return self.decode.num_slots

    def kv_token_bytes_per_device(self) -> int:
        return self.decode.kv_token_bytes_per_device()

    def reset(self) -> None:
        self.prefill.reset()
        self.decode.reset()
        # one clock across both phases: TTFT stamps on the prefill side
        # and finish stamps on the decode side share an origin
        self.decode._t0 = self.prefill._t0
        self.handoff.reset_counters()
        self._requests = []

    def has_unfinished(self) -> bool:
        return self.prefill.has_unfinished() or self.decode.has_unfinished()

    def add_request(self, req: Request,
                    sampling_params: SamplingParams | None = None) -> None:
        if self.prefill._sched is None or self.decode._sched is None:
            self.reset()
        self.prefill.add_request(req, sampling_params)
        self._requests.append(req)

    def step_log(self) -> list:
        """Both engines' ``StepRecord``s since the last call, by start."""
        return sorted(self.prefill.step_log() + self.decode.step_log(),
                      key=lambda r: r.t0_ns)

    def step(self) -> list[RequestOutput]:
        """One disaggregated iteration: prefill chunks, then chain
        transfers (in rid order, stopping at decode backpressure), then
        one fused decode step, then decode-side preemption drain back to
        the prefill queue."""
        outs = self.prefill.step()
        now = self.prefill._now()
        for r in self.prefill.handoff_ready():
            if not self.handoff.transfer(r, now):
                break               # decode side full; chain stays parked
        outs += self.decode.step()
        for r in self.decode._sched.drain_preempted():
            # a decode-side eviction restarts on the PREFILL engine — the
            # chain is recomputed there and handed off again; seeded
            # streams and the emitted watermark make the restart invisible
            self.prefill._sched.requeue(r)
        return outs

    def run(self, requests: Iterable[Request], *, key=None,
            defrag_every: int = 0,
            on_output: Callable[[RequestOutput], None] | None = None
            ) -> ContinuousStats:
        """Serve ``requests`` to completion across both engines; same
        contract as ``ContinuousServeEngine.run``."""
        if self.has_unfinished():
            raise RuntimeError(
                "run() would reset the engines while incrementally-"
                "submitted requests are unfinished; drive step() to "
                "completion first")
        self.reset()
        self.decode.defrag_every = defrag_every
        default = None
        if (key is not None and not self.default_sampling.is_greedy
                and self.default_sampling.seed == 0):
            default = dataclasses.replace(self.default_sampling,
                                          seed=_seed_from_key(key))
        requests = list(requests)
        for r in requests:
            self.add_request(r, sampling_params=default)
        pe, de = self.prefill._sched, self.decode._sched
        records = []
        while pe.has_work() or de.has_work():
            if not pe.running and not de.running:
                nxt_t = pe.next_arrival()
                if nxt_t is None:
                    break
                time.sleep(max(nxt_t - self.prefill._now(), 0.0))
            for o in self.step():
                if on_output is not None:
                    on_output(o)
            records += self.step_log()

        results = {r.rid: np.asarray(r.tokens[:r.max_new_tokens], np.int32)
                   for r in requests}
        per_request = {r.rid: {"preemptions": r.preemptions,
                               "chunks": r.chunks,
                               "shared_tokens": r.shared_tokens,
                               "ttft": r.ttft,
                               "tpot": r.tpot,
                               "finish_time": r.finish_time,
                               "spec_windows": r.spec_windows,
                               "spec_accepted": r.spec_accepted}
                       for r in requests}
        outputs = {r.rid: self.decode._make_output(r, [], finished=True)
                   for r in requests}
        pf, dc, ho = self.prefill, self.decode, self.handoff
        return ContinuousStats(
            results=results,
            **_step_counts(records, dc.num_slots),
            wall=pf._now(),
            preemptions=sum(r.preemptions for r in requests),
            prompt_tokens=pf.cache.lookup_tokens,
            prefix_hit_tokens=pf.cache.hit_tokens,
            cow_events=pf.cache.cow_events + dc.cache.cow_events,
            spec_windows=dc._spec_windows,
            spec_drafted=dc._spec_drafted,
            spec_accepted=dc._spec_accepted,
            handoffs=ho.transfers,
            handoff_pages=ho.pages_moved,
            handoff_bytes=ho.bytes_moved,
            handoff_shared_tokens=ho.shared_tokens,
            per_request=per_request,
            outputs=outputs)


def _step_counts(records, num_slots: int) -> dict:
    """``ContinuousStats``' step counters from a run's ``StepRecord``s (of
    both engines when disaggregated): decode iterations and their mean
    occupancy of ``num_slots``, chunk rows and prompt tokens computed, and
    host time by phase."""
    slots = [r.decode_slots for r in records if r.decode_slots]
    return dict(
        steps=len(slots),
        occupancy=sum(n / num_slots for n in slots) / max(len(slots), 1),
        chunks=sum(r.prefill_rows for r in records),
        prefill_tokens=sum(r.prefill_tokens for r in records),
        host_ms=phase_ms(records))


def serve_step_fn(model: Model):
    """The bare decode step (one token, KV cache) — the function the
    dry-run lowers for ``decode_*`` / ``long_*`` shapes."""

    def serve_step(params, tokens, cache, cur_pos):
        logits, new_cache = model.decode_step(params, tokens, cache, cur_pos)
        return sampling.greedy(logits), new_cache

    return serve_step


def prefill_step_fn(model: Model):
    """Forward over the full prompt — lowered for ``prefill_*`` shapes."""

    def prefill_step(params, batch):
        return model.forward(params, batch)

    return prefill_step
