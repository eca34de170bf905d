"""Operations and bytes that decoding with latent attention and a held
expert share needs, counted from the model's shapes (a configuration
file's ``model`` block: DeepSeek-V2's published names, with
``n_routed_experts`` the experts held here and
``n_routed_experts_published`` the router's width).

These are the numerators of ``mla_attn_roofline``, ``decode_hbm_roofline``
and ``mla_moe_step_mfu``.  They count what the model's mathematics asks of
one decode token at its live context (every position up to its own), in
the absorbed form decode runs: each head scores the cached latent and
rotary key of a position (``r + rope`` lanes) and takes its value from the
latent (``r`` lanes), so a position costs ``2 * H * (2r + rope)``
operations and ``(r + rope) * 2`` bytes a layer.  They never count what a
kernel happens to read: lanes of padding, or pages past a slot's
position.  Weights are counted at bfloat16, each read once a step.
"""
from __future__ import annotations

BF16 = 2


def dims(model: dict) -> dict:
    m = model
    return {"L": m["num_hidden_layers"], "d": m["hidden_size"],
            "h": m["num_attention_heads"], "nope": m["qk_nope_head_dim"],
            "rope": m["qk_rope_head_dim"], "v": m["v_head_dim"],
            "r": m["kv_lora_rank"], "ff": m["intermediate_size"],
            "eff": m["moe_intermediate_size"],
            "E": m["n_routed_experts_published"],
            "shared": m["n_shared_experts"],
            "dense": m["first_k_dense_replace"], "V": m["vocab_size"]}


def latent_attn_work(model: dict, live_tokens: int) -> tuple[int, int]:
    """(operations, bytes) of one decode query's latent attention over
    ``live_tokens`` positions, summed over layers: each live latent row
    read once, the absorbed query read and the context written (bf16)."""
    m = dims(model)
    lat = m["r"] + m["rope"]
    flops = 2 * m["h"] * (lat + m["r"]) * live_tokens
    nbytes = (live_tokens * lat + m["h"] * lat + m["h"] * m["r"]) * BF16
    return m["L"] * flops, m["L"] * nbytes


def expert_params(model: dict) -> int:
    """One routed expert's SwiGLU weights."""
    m = dims(model)
    return 3 * m["d"] * m["eff"]


def step_params(model: dict) -> int:
    """Weights every decode token multiplies whatever its routing: each
    layer's absorbed attention projections (q, the latent and rotary key,
    q_nope into the latent through W_uk, the context out through W_uv, the
    output), the dense layers' MLP, the MoE layers' router and shared
    experts, and the head (the embedding is a lookup)."""
    m = dims(model)
    h, d, r = m["h"], m["d"], m["r"]
    attn = (d * h * (m["nope"] + m["rope"]) + d * (r + m["rope"])
            + r * h * m["nope"] + r * h * m["v"] + h * m["v"] * d)
    moe_layers = m["L"] - m["dense"]
    return (m["L"] * attn + m["dense"] * 3 * d * m["ff"]
            + moe_layers * (d * m["E"] + 3 * d * m["eff"] * m["shared"])
            + d * m["V"])


def decode_flops(model: dict, live_tokens: int) -> int:
    """One decode token at ``live_tokens`` of context, without its routed
    experts (those are counted by the rows they took)."""
    return 2 * step_params(model) + latent_attn_work(model, live_tokens)[0]


def expert_row_flops(model: dict) -> int:
    """One row through one routed expert."""
    return 2 * expert_params(model)
