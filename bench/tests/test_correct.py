"""``correct`` comes out false where it must: for the control (the
reference one precision lower in the program's place) and for a run whose
timed path is broken underneath, once per fault a serving cell can have.

Of the contract's faults, "half of the batch left out" and "the exchange
between chips left out" do not apply to these cells: serving has no
batch mean, and every cell runs on one chip."""
import pytest

from bench.lib import check, serve
from bench.tests import harness

SEED = 2**31 + 21


def test_control_fails_where_the_program_passes():
    config = harness.data("tiny.json")
    run = serve.Run(config, harness.data("tiny_backlog.json"), SEED, 2.0)
    run.run()
    chosen = check.sample(run.tracks.values(), SEED, 3)
    run.free()
    assert any(t.finished for t in chosen)
    sound = check.compare(config, SEED, chosen)
    control = check.control(config, SEED, chosen)
    assert set(sound) == set(control) == {"logit_gap", "logprob_err"}
    assert check.passed(sound)
    assert not check.passed(control)
    for name, c in sound.items():
        assert c["value"] <= c["limit"] < control[name]["value"], name


def _altered_token(monkeypatch):
    """Every sampled token replaced by its neighbour where it is made."""
    from repro.runtime import sampling
    orig = sampling.sample_slots

    def wrong(logits, *a, **kw):
        tok, lp = orig(logits, *a, **kw)
        return (tok + 1) % logits.shape[-1], lp
    monkeypatch.setattr(sampling, "sample_slots", wrong)


def _state_unchanged(monkeypatch):
    """The decode step hands back the KV pools it was given: its own
    token's keys and values are never written."""
    from repro.models.model import Model
    orig = Model.decode_step_paged

    def stale(self, params, tokens, pools, *a, **kw):
        out = orig(self, params, tokens, pools, *a, **kw)
        return (out[0], pools) + tuple(out[2:])
    monkeypatch.setattr(Model, "decode_step_paged", stale)


def _altered_logprob(monkeypatch):
    """Every returned logprob moved by a tenth where it is made; the
    tokens are left as they were."""
    from repro.runtime import sampling
    orig = sampling.sample_slots

    def wrong(logits, *a, **kw):
        tok, lp = orig(logits, *a, **kw)
        return tok, lp - 0.1
    monkeypatch.setattr(sampling, "sample_slots", wrong)


@pytest.mark.parametrize("fault, number", [
    (_altered_token, "logit_gap"), (_state_unchanged, "logit_gap"),
    (_altered_logprob, "logprob_err")])
@pytest.mark.parametrize("name", ["tiny.json", "tiny_window.json"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, number, name):
    fault(monkeypatch)
    res = harness.execute(harness.data(name),
                          harness.data("tiny_backlog.json"), seed=SEED)
    assert res["correct"] is False
    c = res["checks"][number]
    assert c["value"] > c["limit"]
