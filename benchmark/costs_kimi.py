"""What Kimi-K2 (DeepSeek-V3's block) needs, from the configuration's
shapes: FLOPs and bytes of a prefill and of a decode step. Counted as the
mathematics has them (a prompt's attention in the expanded form, 40,960 FLOP
a pair of positions a layer; a decode step's in the absorbed form over the
live rows, 139,264 FLOP a row a layer; the held experts a token as routed;
each touched expert's weights once a step), not as any program executes
them, so a share of a peak built on these cannot pass 100%."""


def _sizes(config):
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "v": config["v_head_dim"],
        "q_rank": config["q_lora_rank"], "rank": config["kv_lora_rank"],
        "layers": config["num_hidden_layers"],
        "dense_layers": min(config["first_k_dense_replace"],
                            config["num_hidden_layers"]),
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "shared": config["n_shared_experts"],
        "held": config["n_routed_experts"],
        "routed": config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"]),
        "per_tok": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
    }


def attention_params(config):
    """Parameters of one layer's attention: q_a, q_b, kv_a, kv_b, o."""
    s = _sizes(config)
    return s["d"] * s["q_rank"] \
        + s["q_rank"] * s["heads"] * (s["nope"] + s["rope"]) \
        + s["d"] * (s["rank"] + s["rope"]) \
        + s["heads"] * (s["nope"] + s["v"]) * s["rank"] \
        + s["heads"] * s["v"] * s["d"]


def dense_ffn_params(config):
    s = _sizes(config)
    return 3 * s["d"] * s["dense_width"]


def expert_params(config):
    """Parameters of one routed expert: gate, up and down."""
    s = _sizes(config)
    return 3 * s["d"] * s["expert_width"]


def expert_layer_fixed_params(config):
    """Of an expert layer, what every token multiplies: the shared expert
    and the router over all the published experts."""
    s = _sizes(config)
    return s["shared"] * expert_params(config) + s["d"] * s["routed"]


def held_parameters(config):
    """Every parameter the chip holds: layers, embedding and head."""
    s = _sizes(config)
    experts = s["layers"] - s["dense_layers"]
    return s["layers"] * attention_params(config) \
        + s["dense_layers"] * dense_ffn_params(config) \
        + experts * (expert_layer_fixed_params(config)
                     + s["held"] * expert_params(config)) \
        + 2 * s["d"] * s["vocab"]


def cache_bytes_a_token(config):
    """Bytes of latent cache one position takes in all layers, at the bytes
    the cell stores it in (`cache_dtype`, else `dtype`)."""
    s = _sizes(config)
    wide = config.get("cache_dtype", config["dtype"]) == "float32"
    return s["layers"] * (s["rank"] + s["rope"]) * (4 if wide else 2)


def expected_held_a_token(config):
    """Assignments a token makes to held experts in one expert layer if the
    router spreads its choices evenly."""
    s = _sizes(config)
    return s["per_tok"] * s["held"] / s["routed"]


def token_product_flops(config, held_a_token=None):
    """FLOPs of the products with weights one token needs in all layers
    (its `held_a_token` held experts a layer among them), without head and
    attention's products of activations."""
    s = _sizes(config)
    if held_a_token is None:
        held_a_token = expected_held_a_token(config)
    experts = s["layers"] - s["dense_layers"]
    return 2 * (s["layers"] * attention_params(config)
                + s["dense_layers"] * dense_ffn_params(config)
                + experts * (expert_layer_fixed_params(config)
                             + held_a_token * expert_params(config)))


def expanded_pair_flops(config):
    """q.k and p.v of one query and one key in all heads, expanded form."""
    s = _sizes(config)
    return 2 * s["heads"] * (s["nope"] + s["rope"]) + 2 * s["heads"] * s["v"]


def absorbed_row_flops(config):
    """One query over one latent row in all heads, absorbed form."""
    s = _sizes(config)
    return 2 * s["heads"] * (s["rank"] + s["rope"]) \
        + 2 * s["heads"] * s["rank"]


def weight_bytes(config, experts_touched, value_bytes=2):
    """Bytes of weights one pass over all layers reads with
    `experts_touched` distinct held experts a layer, and the head."""
    s = _sizes(config)
    experts = s["layers"] - s["dense_layers"]
    return value_bytes * (
        s["layers"] * attention_params(config)
        + s["dense_layers"] * dense_ffn_params(config)
        + experts * (expert_layer_fixed_params(config)
                     + experts_touched * expert_params(config))
        + s["d"] * s["vocab"])


def prefill_flops(config, rows, prompt_len, held_a_token=None):
    s = _sizes(config)
    pairs = prompt_len * (prompt_len + 1) // 2
    return rows * (prompt_len * token_product_flops(config, held_a_token)
                   + s["layers"] * expanded_pair_flops(config) * pairs
                   + 2 * s["d"] * s["vocab"])


def prefill_bytes(config, rows, prompt_len):
    """Every held weight once and the prompt's latent rows written."""
    return weight_bytes(config, _sizes(config)["held"]) \
        + rows * prompt_len * cache_bytes_a_token(config)


def decode_step_flops(config, rows, live, held_a_token=None):
    s = _sizes(config)
    return rows * (token_product_flops(config, held_a_token)
                   + s["layers"] * absorbed_row_flops(config) * live
                   + 2 * s["d"] * s["vocab"])


def decode_step_bytes(config, rows, live, experts_touched):
    return weight_bytes(config, experts_touched) \
        + rows * live * cache_bytes_a_token(config)
