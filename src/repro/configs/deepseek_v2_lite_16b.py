"""DeepSeek-V2-Lite 16B — MLA (kv_lora=512) + MoE 64 routed experts
top-6 with 2 shared experts; first layer dense.  [arXiv:2405.04434]

Routing and rotary values are the released config.json's: softmax scores,
greedy top-6 weights not renormalised (``norm_topk_prob: false``,
``routed_scaling_factor: 1``), and YaRN RoPE (factor 40 over 4096
original positions, ``mscale = mscale_all_dim = 0.707``).

``EP8`` is one chip's share of an 8-way expert-parallel deployment of
the same model: attention, the dense layer, the shared experts, the
embedding and the head whole on every chip; routed experts 0-7 of each
MoE layer on this one.  The router keeps its 64 outputs and top-6; the
rows routed to experts 8-63 are the other chips' work and are not
computed here."""
import dataclasses

from repro.models.common import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,               # first dense layer FFN
    vocab_size=102400, vocab_pad_multiple=512,
    mla=True,
    kv_lora_rank=512,
    rope_head_dim=64,
    v_head_dim=128,
    moe=True,
    n_experts=64,
    n_experts_per_token=6,
    n_shared_experts=2,
    moe_d_ff=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    rope_theta=10000.0,
    rope_scaling=Yarn(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    norm_eps=1e-6,
)

EP8 = dataclasses.replace(CONFIG, name="deepseek-v2-lite-16b-ep8",
                          n_experts_held=8)
