"""The latent-attention expert-share reference against the program's
serving path on the CPU at a reduced size: a DeepSeek-V2-Lite share with
3 layers (the dense one and two MoE layers) of hidden size 64, its
published latent, rotary, value and expert widths, 8 of 64 routed experts
held, chunked prefill into the latent pages and decode through them.

The limits (``bench/tests/data/tiny_mla_moe.json``) are 0.02 on
``logit_gap`` and on ``logprob_err``.  The program keeps its weights and
activations in bfloat16 on the CPU, against the reference's float32: over
six seeds that reads at most 0.0090 (gap) and 0.0122 (logprob), so 0.02
leaves 1.6 times the largest.  The fp8 control reads at least 0.036 and
0.039 on the same seeds, 1.8 and 2.0 times the limits."""
import numpy as np

from bench.lib import check, serve
from bench.reference import mla_moe
from bench.tests import harness

SEED = 2**31 + 3
CONFIG = "tiny_mla_moe.json"


def test_engine_agrees_with_the_reference_and_the_control_does_not():
    config = harness.data(CONFIG)
    run = serve.Run(config, harness.data("tiny_backlog.json"), SEED, 2.0)
    run.run()
    chosen = check.sample(run.tracks.values(), SEED, 3)
    run.free()
    assert any(t.finished for t in chosen)
    # prompts of several prefill chunks, then many tokens decoded through
    # the latent pages
    chunk = config["serving"]["prefill_chunk"]
    assert max(len(t.req.prompt) for t in chosen) > chunk
    assert max(len(t.tokens) for t in chosen) > 8
    sound = check.compare(config, SEED, chosen)
    control = check.control(config, SEED, chosen)
    assert set(sound) == set(control) == {"logit_gap", "logprob_err"}
    for name, c in sound.items():
        assert c["value"] <= c["limit"] < control[name]["value"], name


def test_harness_run_of_the_share_is_correct():
    res = harness.execute(harness.data(CONFIG),
                          harness.data("tiny_backlog.json"), seed=2**31 + 7)
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0


def test_yarn_frequencies_by_hand():
    """DeepSeek-V2-Lite's rotary dims 64, base 1e4, factor 40 over 4096
    positions: pairs 0-10 keep their frequency, 23-31 take 1/40 of it."""
    y = {"factor": 40, "original_max_position_embeddings": 4096,
         "beta_fast": 32, "beta_slow": 1}
    inv = mla_moe.yarn_inv_freq(64, 1e4, y)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11])
    np.testing.assert_allclose(inv[23:], base[23:] / 40)
    m = mla_moe.dims(tuple(sorted(
        (k, mla_moe._hashable(v)) for k, v in harness.data(CONFIG)[
            "model"].items())))
    # 1/sqrt(16 + 64) * (0.1 * 0.707 * ln 40 + 1)^2
    assert abs(mla_moe.softmax_scale(m) - 1.589627 / np.sqrt(80)) < 1e-6
