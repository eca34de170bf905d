"""The plain float32 reference against the program's serving path on the
CPU at a reduced size: chunked prefill into the paged pools, paged decode
(full attention, and a sliding window with ring pages reclaimed past it),
and the benchmark's own weights made again layer by layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import serve, weights
from bench.tests import harness


def test_layer_slices_equal_the_served_weights():
    config = harness.data("tiny.json")
    from repro.models.model import build_model
    model = build_model(serve.program_config(config))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    seed = 2**31 + 11
    params = weights.make_params(shapes, seed)
    base = weights.base_key(seed)
    wq = params["stacks"][0][0]["attn"]["wq"]
    for layer in range(wq.shape[0]):
        again = weights.layer_leaf(base, "stacks/0/0/attn/wq", layer,
                                   wq.shape[1:], wq.dtype)
        assert jnp.array_equal(wq[layer], again)
    emb = weights.leaf_values(weights.leaf_key(base, "embed"),
                              params["embed"].shape, "embed", jnp.bfloat16)
    assert jnp.array_equal(params["embed"], emb)
    ln = np.asarray(params["stacks"][0][0]["ln1"])
    assert ln.dtype == np.float32 and 0.74 < ln.min() and ln.max() < 1.26
    # another seed, other weights
    other = weights.make_params(shapes, seed + 1)
    assert not jnp.array_equal(other["head"], params["head"])


@pytest.mark.parametrize("name", ["tiny.json", "tiny_window.json"])
def test_reference_agrees_with_the_engine(name):
    config = harness.data(name)
    mix = harness.data("tiny_backlog.json")
    res = harness.execute(config, mix, seed=2**31 + 3)
    assert res["correct"], res
    assert set(res["checks"]) == {"logit_gap", "logprob_err"}
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_window_contexts_pass_the_window():
    """The windowed configuration's served requests run past its window,
    so ring pages were reclaimed under the comparison."""
    config = harness.data("tiny_window.json")
    mix = harness.data("tiny_backlog.json")
    run = serve.Run(config, mix, 5, 2.0)
    run.run()
    longest = max(len(t.req.prompt) + len(t.tokens)
                  for t in run.tracks.values())
    run.free()
    assert longest > 2 * config["model"]["sliding_window"]
