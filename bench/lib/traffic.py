"""Seeded traffic for the benchmark: one general generator, driven by the
parameters of a mix file (``bench/traffic/<mix>.json``).

The clipped-lognormal lengths are copied from the program's fleet
generator (``repro.fleet.traffic``, ``LengthMix.sample``), so that a change
to the program cannot change the traffic it is measured on.  What differs
from the copy:

* A mix is a backlog: every request is due when the window opens, and the
  first ``num_slots`` are filled in set-up to a depth drawn over their
  span, so the window sees steady-state contexts.
* Lengths and set-up depths are *stratified*: ``n`` draws are the
  distribution's quantiles at ``(i + 0.5) / n``, paired (prompt with
  output with depth) and put in an order by a stream that is the same for
  every seed.  Every seed then offers the same work in the same sequence:
  the slots filled in set-up hold the same contexts, and the requests
  that refill them come in the same sizes.  The seed draws every token of
  every prompt (and, elsewhere, the weights and the sampled draws).

A mix file holds::

    {"requests": N,
     "prompt":  {"mean", "sigma", "min", "max"},
     "output":  {"mean", "sigma", "min", "max"},
     "sampled_every": 16, "temperature": 0.8, "top_p": 0.95,
     "check_requests": 4}

``check_requests`` is the size of the sample ``correct`` is read on.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

SCHEDULE_SEED = 0x5eed          # sizes and their order: the same every seed


@dataclasses.dataclass
class Req:
    """One request of a run, before it is submitted."""
    rid: int
    prompt: np.ndarray         # int32 token ids the engine is given
    output_len: int            # tokens the engine is asked for
    greedy: bool
    depth: int = 0             # of them, output tokens filled in set-up


def _rng(seed: int, *tag: int) -> np.random.Generator:
    return np.random.default_rng((int(seed), *tag))


def lognormal_lengths(u: np.ndarray, spec: dict) -> np.ndarray:
    """Clipped-lognormal lengths at quantiles ``u``; ``spec`` has the
    mean of the unclipped distribution, its log-space ``sigma`` and the
    clip range (the copy's ``LengthMix.sample``, by inverse CDF)."""
    from statistics import NormalDist
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - 0.5 * sigma ** 2
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    v = np.exp(mu + sigma * z)
    return np.clip(np.round(v), spec["min"], spec["max"]).astype(np.int64)


def _sizes(m: int, mix: dict, fixed: np.random.Generator):
    """``m`` (prompt, output) lengths at stratified quantiles, paired and
    ordered by ``fixed``."""
    u = (np.arange(m) + 0.5) / m
    plen = lognormal_lengths(u, mix["prompt"])
    olen = lognormal_lengths(fixed.permutation(u), mix["output"])
    order = fixed.permutation(m)
    return plen[order], olen[order]


def generate(mix: dict, seed: int, *, vocab: int,
             num_slots: int) -> list[Req]:
    """The requests of one run, in submission order: the first
    ``num_slots`` fill the slots in set-up."""
    n = int(mix["requests"])
    if n <= num_slots:
        raise ValueError(f"a backlog of {n} requests does not outlast "
                         f"{num_slots} slots")
    fixed = np.random.default_rng(SCHEDULE_SEED)
    # the filled slots and the rest are stratified apart, so the contexts
    # set-up builds are the same set however many requests follow
    fp, fo = _sizes(num_slots, mix, fixed)
    fd = fixed.permutation((np.arange(num_slots) + 0.5) / num_slots)
    rp, ro = _sizes(n - num_slots, mix, fixed)
    plen, olen = np.concatenate([fp, rp]), np.concatenate([fo, ro])
    every = int(mix.get("sampled_every", 0))
    reqs = []
    for i in range(n):
        tail = _rng(seed, 2, i).integers(0, vocab, int(plen[i])).astype(
            np.int32)
        depth = 0
        if i < num_slots:
            # a slot caught mid-request: the prompt plus a uniform share
            # of its output is already context when the window opens, so
            # that share joins the prompt the engine prefills in set-up
            depth = int(fd[i] * int(olen[i]))
        done = _rng(seed, 3, i).integers(0, vocab, depth).astype(np.int32)
        reqs.append(Req(rid=i, prompt=np.concatenate([tail, done]),
                        output_len=int(olen[i]) - depth,
                        greedy=not (every and i % every == every - 1),
                        depth=depth))
    return reqs
