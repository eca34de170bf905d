"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the greedy
requests the program served (drawn from the seed, the longest always in
it, and about half of the rest from those that finished) is run through
the configuration's plain reference: every prompt with its served tokens,
teacher-forced.  Two numbers are read over the sample, each compared with
its limit in the configuration file, which was set from sound runs of the
program and from the reference computed one precision lower (PERF.md):

* ``logit_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best logit;
* ``logprob_err``: the widest distance between the logprob the program
  returned for a served token and the reference's log-softmax at that
  token, over the finished requests in the sample (the program returns
  logprobs with a request's last record).  Greedy tokens only: a sampled
  token's logprob is under the sampler's filtered candidate set, which
  the reference does not copy.
"""
from __future__ import annotations

import importlib

import numpy as np


def sample(tracks, seed: int, k: int):
    """Up to ``k`` greedy requests with served tokens: the longest, then
    about half of the rest from those that finished, the others from the
    unfinished; both drawn from the seed."""
    pool = [t for t in tracks if t.req.greedy and t.tokens]
    if not pool:
        return []
    pool.sort(key=lambda t: (-(len(t.req.prompt) + len(t.tokens)), t.req.rid))
    rng = np.random.default_rng((int(seed), 0xc4ec))
    done = [t for t in pool[1:] if t.finished]
    live = [t for t in pool[1:] if not t.finished]
    n_done = min(len(done), k // 2)
    n_live = min(len(live), k - 1 - n_done)
    n_done = min(len(done), k - 1 - n_live)
    pick = ([done[i] for i in sorted(rng.choice(len(done), n_done, False))]
            + [live[i] for i in sorted(rng.choice(len(live), n_live, False))])
    return [pool[0]] + sorted(pick, key=lambda t: t.req.rid)


def sequences(chosen):
    """(token sequences, scored positions, served tokens, served logprobs)
    for the reference: prompt + served tokens but the last, scored at every
    position that predicted a served token.  A logprob is NaN where the
    program returned none (an unfinished request), and inf where it
    returned another count than it served tokens."""
    seqs, at, toks, lps = [], [], [], []
    for t in chosen:
        p = len(t.req.prompt)
        served = np.asarray(t.tokens, np.int32)
        seqs.append(np.concatenate([t.req.prompt, served[:-1]]))
        at.append(p - 1 + np.arange(len(served)))
        toks.append(served)
        if t.logprobs is None:
            lps.append(np.full(len(served), np.nan))
        elif len(t.logprobs) != len(served):
            lps.append(np.full(len(served), np.inf))
        else:
            lps.append(np.asarray(t.logprobs, np.float64))
    return seqs, at, np.concatenate(toks), np.concatenate(lps)


def numbers(ref_logits, tokens, logprobs) -> dict:
    """The numbers compared, against the reference logits (N, V): the
    widest logit gap of ``tokens``, and the widest logprob distance where
    ``logprobs`` is not NaN (left out where it is NaN everywhere)."""
    import jax
    import jax.numpy as jnp
    tok = jnp.asarray(tokens, jnp.int32)
    at = jnp.take_along_axis(ref_logits, tok[:, None], axis=-1)[:, 0]
    gap = np.asarray(jnp.max(ref_logits, axis=-1) - at, np.float64)
    out = {"logit_gap": float(gap.max())}
    has = ~np.isnan(logprobs)
    if has.any():
        ref_lp = np.asarray(at - jax.nn.logsumexp(ref_logits, axis=-1),
                            np.float64)
        out["logprob_err"] = float(np.abs(logprobs - ref_lp)[has].max())
    return out


def with_limits(config: dict, values: dict) -> dict:
    limits = config["check"]["limits"]
    return {k: {"value": v, "limit": float(limits[k])}
            for k, v in values.items()}


def reference(config: dict):
    return importlib.import_module(f"bench.reference.{config['reference']}")


def compare(config: dict, seed: int, chosen) -> dict:
    """The numbers compared, each with its limit, for the served sample."""
    if not chosen:                     # nothing served: not correct
        return with_limits(config, {"logit_gap": 1e30})
    seqs, at, toks, lps = sequences(chosen)
    ref = reference(config)
    logits = ref.logits(config["model"], config["serving"], seed, seqs, at)
    return with_limits(config, numbers(logits, toks, lps))


def control(config: dict, seed: int, chosen) -> dict:
    """The control: the reference one precision lower in the program's
    place, read at the same prompts and served tokens, each number with
    its limit.  At each position it puts its own first token, with its
    own log-softmax there where the program returned a logprob."""
    import jax
    import jax.numpy as jnp
    seqs, at, _, lps = sequences(chosen)
    ref = reference(config)
    exact = ref.logits(config["model"], config["serving"], seed, seqs, at)
    low = ref.logits(config["model"], config["serving"], seed, seqs, at,
                     quant="fp8")
    first = jnp.argmax(low, axis=-1)
    low_lp = (jnp.max(low, axis=-1) - jax.nn.logsumexp(low, axis=-1))
    own = np.where(np.isnan(lps), np.nan, np.asarray(low_lp, np.float64))
    return with_limits(config, numbers(exact, np.asarray(first), own))


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
