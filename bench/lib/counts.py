"""Operations and bytes that the model needs, counted from its shapes.

These are the numerators of ``step_mfu`` and ``paged_attn_roofline``.  They
count what the model's mathematics asks for at the positions that were
really served (live context: tokens up to the query's position and inside
its sliding window), never what a kernel happens to walk: a kernel that
stops loading dead pages must not change its own yardstick.

``model`` is a configuration file's ``model`` block (published names).
"""
from __future__ import annotations

BF16 = 2


def _dims(model: dict) -> tuple[int, int, int, int, int, int, int]:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    kvh = model["num_key_value_heads"]
    hd = model.get("head_dim") or d // h
    return (model["num_hidden_layers"], d, h, kvh, hd,
            model["intermediate_size"], model["vocab_size"])


def matmul_params(model: dict) -> int:
    """Weights that multiply each token: attention and MLP projections of
    every layer and the output head (the embedding is a lookup)."""
    n_l, d, h, kvh, hd, ff, v = _dims(model)
    per_layer = d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * ff
    return n_l * per_layer + d * v


def live(context: int, model: dict) -> int:
    """Keys a query at sequence index ``context - 1`` attends to."""
    w = model.get("sliding_window")
    return min(context, w) if w else context


def attn_flops(model: dict, live_tokens: int) -> int:
    """Scores and weighted values of one query over ``live_tokens`` keys,
    all layers and heads (2 operations per multiply-add, twice)."""
    n_l, _, h, _, hd, _, _ = _dims(model)
    return 4 * n_l * h * hd * live_tokens


def token_flops(model: dict, live_tokens: int) -> int:
    """One token through the whole model at that live context."""
    return 2 * matmul_params(model) + attn_flops(model, live_tokens)


def prefill_flops(model: dict, first: int, last: int) -> int:
    """Prompt positions ``first .. last-1`` computed, each attending to
    its own causal (and windowed) context."""
    n = last - first
    w = model.get("sliding_window")
    ctx = 0
    for p in range(first, last):
        ctx += min(p + 1, w) if w else p + 1
    return 2 * matmul_params(model) * n + attn_flops(model, 1) * ctx


def decode_attn_work(model: dict, live_tokens: int) -> tuple[int, int]:
    """(operations, bytes) the paged decode attention needs for one query
    over ``live_tokens`` keys, summed over layers: the live K and V rows
    read once, the query read and the output written (bf16)."""
    n_l, _, h, kvh, hd, _, _ = _dims(model)
    kv_bytes = 2 * live_tokens * kvh * hd * BF16
    qo_bytes = 2 * h * hd * BF16
    return attn_flops(model, live_tokens), n_l * (kv_bytes + qo_bytes)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time for the work and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bytes")
