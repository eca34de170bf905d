"""FLOP and byte counts from shapes, against hand counts, and the peaks
table."""
import json
import os

import pytest

from bench.lib import counts, peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def model(name):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def test_phi3_decode_step_by_hand():
    m = model("phi3")
    # per layer: q, k, v, o 4 x 3072 x 3072; MLP 3 x 3072 x 8192
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert counts.matmul_params(m) == 32 * per_layer + 3072 * 32064
    # one slot at context 800: K and V of 800 tokens, 32 heads x 96, bf16,
    # 32 layers; q in and out back, 2 x 3072 x 2 bytes a layer
    flops, nbytes = counts.decode_attn_work(m, 800)
    assert nbytes == 32 * (2 * 800 * 32 * 96 * 2 + 2 * 3072 * 2)
    assert flops == 4 * 32 * 32 * 96 * 800
    assert counts.live(800, m) == 800            # full attention: no cap
    assert counts.token_flops(m, 800) == 2 * counts.matmul_params(m) + flops
    # a decode step of 16 such slots is bound by bytes
    t, bound = counts.least_time(16 * flops, 16 * nbytes,
                                 peaks.PEAKS["TPU v5 lite"])
    assert bound == "bytes"
    assert t == pytest.approx(16 * nbytes / 819e9)


def test_danube_window_caps_live_tokens():
    m = model("danube")
    assert counts.live(3000, m) == 3000
    assert counts.live(9000, m) == 4096          # 4096-token window
    flops, nbytes = counts.decode_attn_work(m, counts.live(9000, m))
    # 8 KV heads x 80, K and V, bf16, 24 layers: 61,440 bytes a token
    assert nbytes == 24 * (2 * 4096 * 8 * 80 * 2 + 2 * 2560 * 2)
    assert 4096 * 2 * 8 * 80 * 2 * 24 == 4096 * 61440
    assert flops == 4 * 24 * 32 * 80 * 4096


def test_prefill_flops_counts_each_position_in_its_window():
    m = dict(model("danube"), sliding_window=4)
    # positions 2..5: contexts 3, 4, 4 (capped), 4
    got = counts.prefill_flops(m, 2, 6)
    assert got == 2 * counts.matmul_params(m) * 4 \
        + counts.attn_flops(m, 1) * (3 + 4 + 4 + 4)


def test_peaks_table_is_keyed_by_device_kind():
    p = peaks.for_kind("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v4")
