"""What Keye-VL-2.0's language model needs, from the configuration's shapes:
FLOPs of a prefill and bytes of a decode step. Counted as the mathematics
has them (attention over the kept keys only, experts a token as routed,
each distinct expert's weights once a step), not as any program executes
them, so a share of a peak built on these cannot pass 100%."""


def _sizes(config):
    sa = config["sa_config"]
    d, width = config["hidden_size"], config["head_dim"]
    return {
        "d": d, "width": width, "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "layers": config["num_hidden_layers"],
        "experts": config["num_experts"],
        "per_tok": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "vocab": config["vocab_size"], "topk": sa["topk"],
        "i_heads": sa["indexer_num_heads"], "i_width": sa["indexer_head_dim"],
    }


def layer_dense_params(config):
    """Parameters of one layer that every token multiplies: q, k, v, o, the
    indexer's three projections and the router."""
    s = _sizes(config)
    attention = s["d"] * s["heads"] * s["width"] * 2 \
        + 2 * s["d"] * s["kv_heads"] * s["width"]
    indexer = s["d"] * (s["i_heads"] * s["i_width"] + s["i_width"]
                        + s["i_heads"])
    return attention + indexer + s["d"] * s["experts"]


def expert_params(config):
    """Parameters of one expert: gate, up and down."""
    s = _sizes(config)
    return 3 * s["d"] * s["expert_width"]


def token_product_flops(config):
    """FLOPs of the matrix products one token needs in all layers (its
    `num_experts_per_tok` experts among them), without head and attention."""
    s = _sizes(config)
    return 2 * s["layers"] * (layer_dense_params(config)
                              + s["per_tok"] * expert_params(config))


def kept_positions(config, length):
    """Sum over the queries at 0..length-1 of the positions each attends:
    min(t + 1, topk)."""
    full = min(length, _sizes(config)["topk"])
    return full * (full + 1) // 2 + (length - full) * full


def scored_positions(length):
    """Sum over the queries at 0..length-1 of the positions the indexer
    scores: t + 1."""
    return length * (length + 1) // 2


def prefill_flops(config, rows, prompt_len):
    """FLOPs of prefilling `rows` prompts of `prompt_len`: products,
    attention (q.k and p.v over the kept keys), the indexer's scores over
    the live positions, and the head on each prompt's last row."""
    s = _sizes(config)
    attention = 4 * s["heads"] * s["width"] * kept_positions(config,
                                                             prompt_len)
    indexer = 2 * s["i_heads"] * s["i_width"] * scored_positions(prompt_len)
    return rows * (prompt_len * token_product_flops(config)
                   + s["layers"] * (attention + indexer)
                   + 2 * s["d"] * s["vocab"])


def decode_step_bytes(config, rows, live, distinct_experts, value_bytes=2):
    """Bytes one decode step of `rows` rows must read with `live` positions
    cached a row: per layer the dense weights, `distinct_experts` experts
    (the mean number of different experts the step's rows chose), every live
    indexer key and the kept keys and values; then the head."""
    s = _sizes(config)
    kept = min(live, s["topk"])
    layer = layer_dense_params(config) \
        + distinct_experts * expert_params(config) \
        + rows * live * s["i_width"] \
        + rows * kept * 2 * s["kv_heads"] * s["width"]
    return value_bytes * (s["layers"] * layer + s["d"] * s["vocab"])
