"""The latent-attention expert-share counts at DeepSeek-V2-Lite's published
widths, worked out by hand, and the new metrics' readers on a record with
nothing of theirs to read."""
import json
import os
import types

from bench.lib import mla_moe_counts as mc
from bench.metrics import decode_hbm_roofline, mla_attn_roofline
from bench.tests import harness

with open(os.path.join(harness.ROOT, "bench", "configs", "dsv2lite.json")) as f:
    MODEL = json.load(f)["model"]


def test_counts_at_published_widths():
    # attention a layer: q 2048x16x192 + latent and rotary key 2048x576
    # + W_uk 512x16x128 + W_uv 512x16x128 + out 16x128x2048 = 13,762,560;
    # 27 layers, the dense MLP 3x2048x10944, 26 x (router 2048x64 + shared
    # 3x2048x2816), head 2048x102400
    assert mc.step_params(MODEL) == (27 * 13_762_560 + 67_239_936
                                     + 26 * 17_432_576 + 209_715_200)
    assert mc.expert_params(MODEL) == 3 * 2048 * 1408
    flops, nbytes = mc.latent_attn_work(MODEL, 1000)
    assert flops == 27 * 2 * 16 * (576 + 512) * 1000
    assert nbytes == 27 * 2 * (1000 * 576 + 16 * 576 + 16 * 512)
    assert mc.decode_flops(MODEL, 1000) == 2 * mc.step_params(MODEL) + flops


def test_trace_readers_read_nothing_without_a_trace():
    rec = types.SimpleNamespace(trace=None, steps=[], config={"model": MODEL})
    assert mla_attn_roofline.read(rec) is None
    assert decode_hbm_roofline.read(rec) is None
