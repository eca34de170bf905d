"""One run of one serving cell: set-up, the measured window, the record.

The path driven is the one users call: ``LLMEngine(backend="continuous")``
then ``add_request`` / ``step()``, which runs ``ContinuousServeEngine.step``
over the paged pools and, on a TPU, the Pallas paged-decode kernel.  The
program sees only the generated requests; everything else here (the
clock, what counts as a token in the window, which step did what) is the
benchmark's own.

Set-up, all of it inside ``setup_s``: build the model and the weights,
the engine, run warm-up requests whose shapes are those the window will
use, fill the slots and submit the rest of the backlog.  The window then
runs ``step()`` for ``seconds``, and every token is stamped with the host
clock when the ``step()`` that emitted it returns (its results have been
read back by then).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import sys
import time

import numpy as np

from bench.lib import traffic as traffic_lib
from bench.lib import weights as weights_lib

TRACE_SECONDS = 10.0
# counts of requests that prefill together: the window's refills (slots
# freed in one step) and, at the largest, the slot fill in set-up
PREFILL_BUCKETS = (1, 2, 4)

# program-config fields the configuration file states, by published name
MODEL_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "sliding_window": "sliding_window",
    "tie_word_embeddings": "tie_embeddings",
}


def span(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Track:
    """What the benchmark saw of one request."""
    req: traffic_lib.Req
    submitted: float | None = None       # host clock
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)
    shared: int = 0                      # prompt tokens from shared pages
    finished: bool = False
    logprobs: list | None = None         # the finished record's, if any


@dataclasses.dataclass
class Step:
    """One ``step()`` call in the window."""
    t0: float
    t1: float
    decode_ctx: list                     # context read by each decode token


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: its registry
    entry with the file's sizes (a no-op for an unreduced model)."""
    import dataclasses as dc
    from repro.configs import get_config
    cfg = get_config(config["arch"])
    kw = {MODEL_FIELDS[k]: v for k, v in config["model"].items()
          if k in MODEL_FIELDS}
    kw["vocab_pad_multiple"] = config["serving"]["vocab_pad_multiple"]
    return dc.replace(cfg, **kw)


def warm_prompt_lengths(mix: dict, serving: dict) -> list[int]:
    """Prompt lengths whose chunks, run together in one bucket, use every
    page-table width (pow-2 block count) that the mix's admissions use."""
    page, c = serving["page_size"], serving["prefill_chunk"]
    max_blocks = -(-serving["max_len"] // page)
    lo = mix["prompt"]["min"]
    # set-up prompts carry outputs too
    hi = min(mix["prompt"]["max"] + mix["output"]["max"],
             serving["max_len"] - 1)

    def widths(start, n):
        out, pos = set(), start
        while pos < n:
            pos = min(pos + c, n)
            need = -(-pos // page)
            out.add(min(1 << (need - 1).bit_length(), max_blocks))
        return out

    want = set()
    for n in range(lo, hi + 1):
        want |= widths(0, n)
    # few prompts (from 0) covering every width: the longest first, whose
    # chunks walk the multi-chunk widths, then one per narrower width
    lengths, covered = [], set()
    for w in sorted(want, reverse=True):
        if w in covered:
            continue
        n = min(w * page, serving["max_len"] - 1)
        lengths.append(n)
        covered |= widths(0, n)
    return lengths


class Run:
    """Drives one engine through set-up and the window."""

    def __init__(self, config: dict, mix: dict, seed: int, seconds: float):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.seconds, self.clock = float(seconds), time.perf_counter
        self.serving = config["serving"]
        self.tracks: dict[int, Track] = {}
        self.steps: list[Step] = []
        self.window = None
        self.compiles = collections.Counter()
        self.traces = collections.Counter()
        self._phase = "setup"

    # -- set-up -------------------------------------------------------------
    def build(self):
        import jax
        import jax.numpy as jnp
        from repro.models.model import build_model
        from repro.runtime.llm import LLMEngine

        cfg = program_config(self.config)
        self.model = build_model(cfg)
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.params = jax.block_until_ready(
            weights_lib.make_params(shapes, self.seed))
        s = self.serving
        self.llm = LLMEngine(
            self.model, self.params, backend="continuous",
            max_len=s["max_len"], num_slots=s["num_slots"],
            page_size=s["page_size"], num_pages=s["num_pages"],
            prefill_chunk=s["prefill_chunk"],
            cache_dtype=jnp.dtype(s.get("cache_dtype", "bfloat16")),
            enable_prefix_cache=True)
        self.vocab = cfg.vocab_size

    def sampling(self, req: traffic_lib.Req, max_tokens: int | None = None):
        from repro.runtime.sampling import SamplingParams
        n = req.output_len if max_tokens is None else max_tokens
        if req.greedy:
            return SamplingParams(max_tokens=n, logprobs=True)
        return SamplingParams(temperature=float(self.mix["temperature"]),
                              top_p=float(self.mix["top_p"]),
                              seed=(self.seed + req.rid) & 0x7FFFFFFF,
                              max_tokens=n, logprobs=True)

    def submit(self, req: traffic_lib.Req) -> None:
        with span("bench.add_request"):
            self.llm.add_request(req.prompt, self.sampling(req), rid=req.rid)
        self.tracks[req.rid] = Track(req=req, submitted=self.clock())

    def step(self) -> list:
        with span("bench.step"):
            t0 = self.clock()
            outs = self.llm.step()
            t1 = self.clock()
        with span("bench.outputs"):
            ctx = []
            for o in outs:
                tr = self.tracks.get(o.rid)
                if tr is None:                       # a warm-up request
                    continue
                for tok in o.new_token_ids:
                    if tr.tokens:
                        # token j came from the decode step that read token
                        # j-1 at index P + j - 1: a context of P + j tokens
                        ctx.append(len(tr.req.prompt) + len(tr.tokens))
                    tr.tokens.append(int(tok))
                    tr.times.append(t1)
                tr.shared = int(o.metrics.get("shared_tokens", tr.shared))
                if o.finished:
                    tr.finished = True
                    tr.logprobs = o.logprobs
            if self._phase == "window":
                self.steps.append(Step(t0, t1, ctx))
        return outs

    def drain(self, rids) -> None:
        """Step until every request in ``rids`` has its first token."""
        while any(not self.tracks[r].tokens for r in rids):
            self.step()

    def warm_up(self) -> None:
        """Run throw-away requests that use every prefill shape the window
        can: each of ``PREFILL_BUCKETS`` concurrent prefills, at every
        page-table width the mix's prompts need."""
        from repro.runtime.sampling import SamplingParams
        rng = np.random.default_rng((self.seed, 0x3a7))
        lengths = warm_prompt_lengths(self.mix, self.serving)
        rid = -1
        for b in PREFILL_BUCKETS:
            for n in lengths:
                for _ in range(b):
                    prompt = rng.integers(0, self.vocab, n).astype(np.int32)
                    self.llm.add_request(prompt,
                                         SamplingParams(max_tokens=1),
                                         rid=rid)
                    rid -= 1
                while self.llm.has_unfinished():
                    self.llm.step()

    def fill(self, reqs) -> None:
        """Put the first ``num_slots`` requests into the slots at their
        set-up depth, longest first, as many at a time as the largest of
        ``PREFILL_BUCKETS`` (a group's prefill runs in the buckets the
        warm-up ran)."""
        first = sorted(reqs[:self.serving["num_slots"]],
                       key=lambda r: -len(r.prompt))
        g = max(PREFILL_BUCKETS)
        for i in range(0, len(first), g):
            group = first[i:i + g]
            for r in group:
                self.submit(r)
            self.drain([r.rid for r in group])

    # -- the window ---------------------------------------------------------
    def serve(self, t_close: float) -> float:
        """Step while the engine has work, until ``t_close``; returns when
        the last step returned."""
        end = self.clock()
        while end < t_close and self.llm.has_unfinished():
            self.step()
            end = self.clock()
        return end

    def _on_event(self, name, secs, **kw):
        """Counts XLA compilations, and loads from the persistent cache, by
        phase: either is a program the process had not run before, and one
        in the window is a shape the warm-up missed.  Traces and lowerings
        are counted apart: one in the window is a program traced again
        that an in-memory cache then found."""
        if name.endswith(("backend_compile_duration",
                          "cache_retrieval_time_sec")):
            self.compiles[self._phase] += 1
        elif name.endswith(("jaxpr_trace_duration",
                            "jaxpr_to_mlir_module_duration")):
            self.traces[self._phase] += 1

    def run(self, trace_dir: str | None = None):
        """Set-up, then the window; returns the window's (start, end)."""
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        try:
            return self._run(trace_dir)
        finally:
            jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _log(self, what: str) -> None:
        print(f"set-up: {what} at {self.clock() - self._t_build:.1f} s, "
              f"compiles so far {self.compiles['setup']}", file=sys.stderr,
              flush=True)

    def _run(self, trace_dir):
        import jax
        self._t_build = self.clock()
        self.build()
        self._log("engine built")
        reqs = traffic_lib.generate(self.mix, self.seed, vocab=self.vocab,
                                    num_slots=self.serving["num_slots"])
        self.warm_up()
        self._log("warm-up done")
        self.fill(reqs)
        self._log("slots filled")
        for r in reqs:
            if r.rid not in self.tracks:
                self.submit(r)
        self._phase = "window"
        t0 = self.clock()
        self.traced = None
        if trace_dir is not None:
            # the profiler records the window's first TRACE_SECONDS: enough
            # steps for the per-layer metrics, and a trace that is read
            # well inside the run's time limit
            jax.profiler.start_trace(trace_dir)
            with span("bench.window"):
                t = self.serve(t0 + min(TRACE_SECONDS, self.seconds))
            jax.profiler.stop_trace()
            self.traced = (t0, t)
        t1 = self.serve(t0 + self.seconds)
        self._phase = "after"
        self.window = (t0, t1)
        return t0, t1

    def free(self) -> None:
        """Drop the engine, its pools and the weights."""
        self.llm = self.params = self.model = None
        gc.collect()


def quantile(values, q: float) -> float:
    """The ``q`` quantile by linear interpolation (numpy's default)."""
    return float(np.quantile(np.asarray(values, np.float64), q))


def end_to_end(run: Run) -> dict:
    """The end-to-end numbers of a window (host clock)."""
    t0, t1 = run.window
    tokens, gaps = 0, []
    for tr in run.tracks.values():
        times = [t for t in tr.times if t0 <= t <= t1]
        tokens += len(times)
        gaps += list(np.diff(times)) if len(times) > 1 else []
    out = {"output_tok_s": tokens / (t1 - t0), "window_s": t1 - t0,
           "tokens": tokens, "gaps": len(gaps)}
    if gaps:
        out["itl_p95_ms"] = quantile(gaps, 0.95) * 1e3
    return out


def window_requests(run: Run) -> tuple[int, int]:
    """(attempted, failed): requests that had work in the window, and
    those among them that finished with another token count than asked."""
    t0, t1 = run.window
    att = fail = 0
    for tr in run.tracks.values():
        if any(t0 <= t <= t1 for t in tr.times) or (
                tr.submitted is not None and t0 <= tr.submitted <= t1):
            att += 1
            if tr.finished and len(tr.tokens) != tr.req.output_len:
                fail += 1
    return att, fail


def prefill_work(run: Run, t0: float, t1: float) -> list[tuple[int, int]]:
    """(first, last+1) prompt positions computed for each request whose
    first token came in [t0, t1] (prefix-cache hits are not computed)."""
    out = []
    for tr in run.tracks.values():
        if tr.times and t0 <= tr.times[0] <= t1:
            out.append((tr.shared, len(tr.req.prompt)))
    return out
