"""``LLMEngine`` — one request-level generation front-end.

The paper's serving scenario is many concurrent reasoning requests with
long sampled output streams; the execution strategy underneath (static
batch scan, continuous batching over paged KV, speculative draft/target)
is a deployment decision, not an API.  ``LLMEngine`` is the single seam:

    llm = LLMEngine(model, params, backend="continuous", max_len=256,
                    num_slots=8)
    outs = llm.generate(prompts, SamplingParams(temperature=0.8, top_p=0.9,
                                                seed=7, max_tokens=64))

Every backend takes the same per-request ``SamplingParams`` and returns
the same structured ``RequestOutput`` list (token ids, finish_reason,
optional logprobs, timing metrics).  Greedy requests are token-exact
across all three backends; sampled requests are reproducible across the
static and continuous backends (fold_in(seed, pos) streams — see
``runtime.sampling``).  The continuous backend additionally streams
incremental deltas through ``on_output`` / the ``add_request()``/
``step()`` interface; static and speculative execution have no per-token
host loop (that is their point), so they emit one final output per
request.

Deployment sizing is hardware-aware: pass a ``DeploymentSpec``
(``runtime.deployment``) and the paged-KV pool, decode-slot count, and
admission hints derive from the named SKU / HBM-CO stack / weight format
instead of hand-tuned kwargs::

    llm = LLMEngine(model, params,
                    spec=DeploymentSpec(sku="rpu-cu", hbmco="hbmco-768MB",
                                        weight_format="mxfp4",
                                        max_len=4096))
    print(llm.deployment.describe())

Stateful cache layouts (SWA ring pages, SSM state pools —
``runtime.state_cache``) serve through the same façade: the continuous
engine classifies the model's plan and sizes ring/state pools itself.
Future backends (real-TPU serving) plug in behind this façade instead of
growing new ad-hoc entrypoints.
"""
from __future__ import annotations

from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.runtime import sampling
from repro.runtime.engine import (
    ContinuousServeEngine, DisaggServeEngine, RequestOutput, ServeEngine,
)
from repro.runtime.sampling import SamplingParams
from repro.runtime.scheduler import Request

BACKENDS = ("static", "continuous", "speculative")


def _truncate(tokens: list[int], sp: SamplingParams,
              budget: int) -> tuple[list[int], str]:
    """Apply stop-token / budget finish semantics to a pre-generated
    stream (the static scan and speculative windows have fixed trip
    counts; the host applies the finish reason afterwards)."""
    tokens = tokens[:budget]
    for j, t in enumerate(tokens):
        if t in sp.stop_token_ids:
            return tokens[:j + 1], "stop"
    return tokens, "length"


class LLMEngine:
    """One ``generate(prompts, sampling_params)`` API over static,
    continuous, and speculative execution."""

    def __init__(self, model: Model, params: Any, *,
                 backend: str = "continuous", spec=None,
                 max_len: int | None = None,
                 num_slots: int | None = None, page_size: int | None = None,
                 num_pages: int | None = None,
                 prefill_chunk: int | None = None,
                 enable_prefix_cache: bool = True, cache_dtype=None,
                 weight_format: str | None = None,
                 max_top_k: int = sampling.MAX_TOP_K,
                 draft_model: Model | None = None, draft_params: Any = None,
                 gamma: int = 8, speculative=None,
                 default_sampling: SamplingParams | None = None,
                 mesh=None, tp_reduce: str = "auto",
                 disaggregate: bool = False,
                 prefill_mesh=None, decode_mesh=None,
                 prefill_slots: int | None = None,
                 prefill_pages: int | None = None):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {backend!r}")
        if disaggregate and backend != "continuous":
            raise ValueError("disaggregate=True splits the continuous "
                             "backend into phase engines; other backends "
                             "have no prefill/decode split to make")
        if mesh is not None and backend != "continuous":
            raise ValueError(
                "mesh= shards the continuous paged serve path; run the "
                f"{backend!r} backend under an ambient mesh + sharding_rules "
                "context instead")
        if spec is not None and spec.mesh is not None \
                and backend != "continuous":
            raise ValueError("spec.mesh shards the continuous backend only")
        if speculative is not None and backend != "continuous":
            raise ValueError(
                "speculative= configures scheduler-integrated speculation "
                "in the continuous engine; the legacy 'speculative' "
                "backend takes draft_model=/draft_params=/gamma= directly")
        if spec is None:
            # legacy knob defaults (the pre-DeploymentSpec hand-tuned path)
            max_len = 256 if max_len is None else max_len
            num_slots = 8 if num_slots is None else num_slots
            page_size = 16 if page_size is None else page_size
            prefill_chunk = 64 if prefill_chunk is None else prefill_chunk
        elif max_len is None:
            max_len = spec.max_len
        self.model = model
        self.params = params
        self.backend = backend
        self.max_len = max_len
        self.default_sampling = default_sampling or sampling.GREEDY
        self.max_top_k = int(max_top_k)
        self.last_stats = None          # ContinuousStats of the last run
        if backend == "continuous":
            if spec is None and num_pages is None:
                num_pages = 1 + 2 * num_slots * -(-max_len // page_size)
            if disaggregate:
                self._eng = DisaggServeEngine(
                    model, params, num_slots=num_slots, page_size=page_size,
                    num_pages=num_pages, max_len=max_len, spec=spec,
                    prefill_mesh=prefill_mesh if prefill_mesh is not None
                    else mesh,
                    decode_mesh=decode_mesh if decode_mesh is not None
                    else mesh,
                    prefill_slots=prefill_slots, prefill_pages=prefill_pages,
                    sampling_params=self.default_sampling,
                    cache_dtype=cache_dtype, weight_format=weight_format,
                    prefill_chunk=prefill_chunk,
                    enable_prefix_cache=enable_prefix_cache,
                    max_top_k=self.max_top_k, tp_reduce=tp_reduce,
                    speculative=speculative)
            else:
                self._eng = ContinuousServeEngine(
                    model, params, num_slots=num_slots, page_size=page_size,
                    num_pages=num_pages, max_len=max_len, spec=spec,
                    sampling_params=self.default_sampling,
                    cache_dtype=cache_dtype, weight_format=weight_format,
                    prefill_chunk=prefill_chunk,
                    enable_prefix_cache=enable_prefix_cache,
                    max_top_k=self.max_top_k, mesh=mesh, tp_reduce=tp_reduce,
                    speculative=speculative)
        elif backend == "static":
            self._eng = ServeEngine(
                model, params, max_len=max_len, spec=spec,
                sampling_params=self.default_sampling, donate_cache=False,
                cache_dtype=cache_dtype, weight_format=weight_format,
                max_top_k=self.max_top_k)
        else:                            # speculative (legacy dense-cache)
            # with no draft the target drafts for itself ("ideal draft"):
            # every window accepts, output equals the target-only stream.
            # One SpeculativeEngine for the LLMEngine's lifetime: the
            # prefill jits and per-SamplingParams window jits are cached,
            # so repeated prompts stop re-tracing.
            from repro.runtime.speculative import SpeculativeEngine
            self.draft_model = draft_model or model
            self.draft_params = draft_params if draft_model is not None \
                else params
            self.gamma = gamma
            # a DeploymentSpec sizes this backend too (max_len came from it
            # above); the budget is priced with the draft's weights and
            # pool bytes, and the resolved point is kept for inspection
            self._speculative_deployment = (
                spec.resolve(model, params=params, draft=self.draft_model,
                             draft_params=self.draft_params, gamma=gamma)
                if spec is not None else None)
            self._spec = SpeculativeEngine(
                self.draft_model, self.draft_params, model, params,
                gamma=gamma)
            self._eng = None

    # -- mesh introspection (continuous backend) ----------------------------
    @property
    def serve_plan(self):
        """The engine's ``PagedServePlan`` (None off-mesh / other backends)."""
        return getattr(self._eng, "serve_plan", None)

    @property
    def deployment(self):
        """The resolved ``DeploymentSpec`` budget (None without spec=)."""
        if self._eng is None:              # legacy speculative backend
            return self._speculative_deployment
        return getattr(self._eng, "deployment", None)

    def kv_token_bytes_per_device(self) -> int:
        """Per-device pool bytes one cached token costs (continuous only)."""
        if self.backend != "continuous":
            raise ValueError("KV accounting needs backend='continuous'")
        return self._eng.kv_token_bytes_per_device()

    # -- request plumbing ---------------------------------------------------
    def _resolve(self, prompts, sampling_params, max_new_tokens):
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        n = len(prompts)
        if sampling_params is None:
            sps = [self.default_sampling] * n
        elif isinstance(sampling_params, SamplingParams):
            sps = [sampling_params] * n
        else:
            sps = list(sampling_params)
            if len(sps) != n:
                raise ValueError(f"{len(sps)} SamplingParams for "
                                 f"{n} prompts")
        budgets = []
        for p, sp in zip(prompts, sps):
            budget = sp.max_tokens if sp.max_tokens is not None \
                else max_new_tokens
            if budget is None:
                raise ValueError("set SamplingParams.max_tokens or pass "
                                 "max_new_tokens")
            # the continuous engine enforces its own (page-rounded)
            # capacity in add_request; static caches are exactly max_len
            if (self.backend != "continuous"
                    and p.shape[0] + budget > self.max_len):
                raise ValueError(f"max_tokens={budget} exceeds max_len="
                                 f"{self.max_len} for a {p.shape[0]}-token "
                                 f"prompt")
            budgets.append(int(budget))
        return prompts, sps, budgets

    # -- incremental interface (continuous backend) -------------------------
    def add_request(self, prompt, sampling_params: SamplingParams | None = None,
                    *, rid: int | None = None, max_new_tokens: int | None = None,
                    arrival_time: float = 0.0) -> int:
        """Submit one request to the continuous engine; returns its rid.
        Drive with ``step()`` until ``has_unfinished()`` is False."""
        if self.backend != "continuous":
            raise ValueError("add_request()/step() need backend='continuous'")
        (prompt,), (sp,), (budget,) = self._resolve(
            [prompt], sampling_params, max_new_tokens)
        if rid is None:
            rid = getattr(self, "_next_rid", 0)
        # explicit low rids must never rewind the auto-rid counter into
        # collision with live requests
        self._next_rid = max(getattr(self, "_next_rid", 0), rid + 1)
        self._eng.add_request(Request(rid=rid, prompt=prompt,
                                      max_new_tokens=budget, sampling=sp,
                                      arrival_time=arrival_time))
        return rid

    def step(self) -> list[RequestOutput]:
        if self.backend != "continuous":
            raise ValueError("add_request()/step() need backend='continuous'")
        return self._eng.step()

    def step_log(self) -> list:
        """The engine's ``StepRecord``s since the last call (host time by
        phase and what each ``step()`` did; ``runtime.tracing``)."""
        if self.backend != "continuous":
            raise ValueError("step_log() needs backend='continuous'")
        return self._eng.step_log()

    def has_unfinished(self) -> bool:
        return self.backend == "continuous" and self._eng.has_unfinished()

    # -- one-shot interface (all backends) ----------------------------------
    def generate(self, prompts: Iterable, sampling_params=None, *,
                 max_new_tokens: int | None = None,
                 arrival_times: Sequence[float] | None = None,
                 on_output: Callable[[RequestOutput], None] | None = None
                 ) -> list[RequestOutput]:
        """Generate for ``prompts`` (sequences of token ids); returns one
        final ``RequestOutput`` per prompt, in order.

        ``sampling_params``: one ``SamplingParams`` or a per-prompt list.
        ``arrival_times`` (continuous only) replays a ragged arrival trace.
        ``on_output`` streams incremental deltas (continuous) or final
        outputs as each request completes (static / speculative)."""
        prompts, sps, budgets = self._resolve(prompts, sampling_params,
                                              max_new_tokens)
        if arrival_times is not None and self.backend != "continuous":
            raise ValueError("arrival_times needs backend='continuous'")
        if self.backend == "continuous":
            return self._generate_continuous(prompts, sps, budgets,
                                             arrival_times, on_output)
        if self.backend == "static":
            return self._generate_static(prompts, sps, budgets, on_output)
        return self._generate_speculative(prompts, sps, budgets, on_output)

    def _generate_continuous(self, prompts, sps, budgets, arrival_times,
                             on_output):
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=budgets[i],
                        sampling=sps[i],
                        arrival_time=(float(arrival_times[i])
                                      if arrival_times is not None else 0.0))
                for i in range(len(prompts))]
        stats = self._eng.run(reqs, on_output=on_output)
        self.last_stats = stats
        return [stats.outputs[i] for i in range(len(prompts))]

    def _generate_static(self, prompts, sps, budgets, on_output):
        lens = {p.shape[0] for p in prompts}
        if len(lens) != 1:
            raise ValueError(
                "backend='static' batches one prompt length per call "
                f"(got {sorted(lens)}); use backend='continuous' for "
                "ragged prompts")
        batch = jnp.asarray(np.stack(prompts))
        res = self._eng.generate({"tokens": batch},
                                 max_new_tokens=max(budgets),
                                 sampling_params=sps)
        plps = None
        if any(sp.prompt_logprobs for sp in sps):
            # score the prompt with one jitted forward: position k's
            # log-softmax row scores prompt token k+1 (raw model scores —
            # the generation-side processors don't apply to the prompt)
            logits = jax.jit(self.model.forward)(self._eng.params,
                                                 {"tokens": batch})
            ls = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            plps = np.asarray(jnp.take_along_axis(
                ls[:, :-1], batch[:, 1:, None], axis=-1)[..., 0])
        toks = np.asarray(res.tokens)
        outs = []
        for i, sp in enumerate(sps):
            ids, reason = _truncate([int(t) for t in toks[i]], sp, budgets[i])
            lps = ([float(v) for v in np.asarray(res.logprobs)[i, :len(ids)]]
                   if sp.logprobs else None)
            out = RequestOutput(rid=i, new_token_ids=list(ids),
                                token_ids=list(ids), finished=True,
                                finish_reason=reason, logprobs=lps,
                                prompt_logprobs=(
                                    [float(v) for v in plps[i]]
                                    if sp.prompt_logprobs else None),
                                metrics={})
            outs.append(out)
            if on_output is not None:
                on_output(out)
        return outs

    def _generate_speculative(self, prompts, sps, budgets, on_output):
        for sp in sps:
            if sp.repetition_penalty != 1.0 or sp.logit_bias:
                raise ValueError(
                    "backend='speculative' does not support "
                    "repetition_penalty/logit_bias (the continuous "
                    "engine's speculative= mode does — its verify step "
                    "threads the running presence through p and q)")
            if sp.prompt_logprobs:
                raise ValueError(
                    "backend='speculative' does not score prompts; use "
                    "backend='static' or 'continuous' for prompt_logprobs")
        outs = []
        for i, (p, sp, budget) in enumerate(zip(prompts, sps, budgets)):
            stats = self._spec.generate(
                jnp.asarray(p)[None], max_new_tokens=budget,
                sampling_params=sp, key=jax.random.PRNGKey(sp.seed))
            ids, reason = _truncate([int(t) for t in stats.tokens[:budget]],
                                    sp, budget)
            out = RequestOutput(
                rid=i, new_token_ids=list(ids), token_ids=list(ids),
                finished=True, finish_reason=reason, logprobs=None,
                metrics={"windows": stats.windows,
                         "accepted_per_window": stats.mean_accepted})
            outs.append(out)
            if on_output is not None:
                on_output(out)
        return outs
