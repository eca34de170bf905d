"""YaRN rotary scaling and MLA's softmax scale against DeepSeek-V2's
formulas (``yarn_find_correction_range``, the linear-ramp blend of
``inv_freq``, ``yarn_get_mscale``), at values worked out by hand for
DeepSeek-V2-Lite's published ``rope_scaling``."""
import math

import numpy as np

import jax.numpy as jnp

from repro.configs import get_config
from repro.models.common import (Yarn, apply_rope, rope_freqs,
                                 yarn_correction_range, yarn_mscale)

LITE = get_config("deepseek-v2-lite-16b")


def test_published_values_are_stated():
    y = LITE.rope_scaling
    assert (y.factor, y.original_max_position_embeddings, y.beta_fast,
            y.beta_slow, y.mscale, y.mscale_all_dim) == (40, 4096, 32, 1,
                                                         0.707, 0.707)
    assert not LITE.norm_topk_prob and LITE.norm_eps == 1e-6
    share = get_config("deepseek-v2-lite-16b-ep8")
    assert (share.n_experts, share.n_experts_held, share.experts_held) == (
        64, 8, 8)


def test_correction_range_and_mscale_by_hand():
    # dim 64 over base 1e4 and 4096 positions:
    #   64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 -> floor 10
    #   64 ln(4096 / (1 * 2 pi))  / (2 ln 1e4) = 22.51 -> ceil 23
    assert yarn_correction_range(LITE.rope_scaling, 64, 1e4) == (10, 23)
    # 0.1 * 0.707 * ln 40 + 1 = 1.260804
    assert abs(yarn_mscale(40, 0.707) - 1.260804) < 1e-6
    assert yarn_mscale(1.0, 0.707) == 1.0
    # 1/sqrt(128 + 64) * 1.260804^2 = 0.0721688 * 1.589627
    assert abs(LITE.mla_softmax_scale - 0.0721688 * 1.589627) < 1e-6
    plain = get_config("qwen3-14b")
    assert plain.rope_scaling is None


def test_inv_freq_blends_by_the_linear_ramp():
    got = np.asarray(rope_freqs(64, 1e4, LITE.rope_scaling), np.float64)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)   # ramp 0
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    ramp = (15 - 10) / 13                       # pair 15 on the ramp
    np.testing.assert_allclose(
        got[15], base[15] / 40 * ramp + base[15] * (1 - ramp), rtol=1e-6)
    # without scaling: the plain frequencies
    np.testing.assert_allclose(np.asarray(rope_freqs(64, 1e4)), base,
                               rtol=1e-6)


def test_rope_attention_scale_on_cos_and_sin():
    """cos and sin carry mscale(factor, mscale) / mscale(factor,
    mscale_all_dim): 1 for the published pair, so the rotation is a pure
    rotation; another pair stretches it by the ratio."""
    x = jnp.ones((1, 3, 1, 8), jnp.float32)
    pos = jnp.arange(3)[None]
    y = Yarn(factor=40.0, original_max_position_embeddings=4096,
             mscale=0.707, mscale_all_dim=0.707)
    out = apply_rope(x, pos, 1e4, y)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(out), axis=-1),
                               math.sqrt(8), rtol=1e-6)
    y2 = Yarn(factor=40.0, original_max_position_embeddings=4096,
              mscale=1.0, mscale_all_dim=0.0)
    out2 = apply_rope(x, pos, 1e4, y2)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(out2), axis=-1),
                               math.sqrt(8) * yarn_mscale(40.0, 1.0),
                               rtol=1e-6)
