"""What LFM2-MoE needs, from the configuration's shapes: FLOPs and bytes of
a prefill and of a decode step. Counted as the mathematics has them (every
token through its `num_experts_per_tok` experts and no padded row; a causal
pair of positions once in each attention layer, 8,192 FLOP; each touched
expert's weights once a step; the cache at the bytes the cell stores it
in), not as any program executes them, so a share of a peak built on these
cannot pass 100%."""


def _sizes(config):
    layers = config["num_hidden_layers"]
    types = config["layer_types"][:layers]
    heads = config["num_attention_heads"]
    return {
        "d": config["hidden_size"], "heads": heads,
        "groups": config["num_key_value_heads"],
        "head": config["hidden_size"] // heads,
        "kernel": config["conv_L_cache"],
        "layers": layers,
        "conv_layers": sum(kind == "conv" for kind in types),
        "attention_layers": sum(kind == "full_attention" for kind in types),
        "dense_layers": min(config["num_dense_layers"], layers),
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "experts": config["num_experts"],
        "per_tok": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
    }


def conv_params(config):
    """One gated short convolution: in_proj, the depthwise kernel, out_proj."""
    s = _sizes(config)
    return 3 * s["d"] * s["d"] + s["d"] * s["kernel"] + s["d"] * s["d"]


def attention_params(config):
    """One attention layer: q, k, v, out and the two head norms."""
    s = _sizes(config)
    return 2 * s["d"] * s["heads"] * s["head"] \
        + 2 * s["d"] * s["groups"] * s["head"] + 2 * s["head"]


def dense_ffn_params(config):
    s = _sizes(config)
    return 3 * s["d"] * s["dense_width"]


def expert_params(config):
    """One routed expert: w1, w2 and w3."""
    s = _sizes(config)
    return 3 * s["d"] * s["expert_width"]


def router_params(config):
    """The router's matrix and its selection bias."""
    s = _sizes(config)
    return s["d"] * s["experts"] + s["experts"]


def _outside_experts(config):
    """Every parameter of the layers but the experts' own and the table."""
    s = _sizes(config)
    return s["conv_layers"] * conv_params(config) \
        + s["attention_layers"] * attention_params(config) \
        + s["layers"] * 2 * s["d"] \
        + s["dense_layers"] * dense_ffn_params(config) \
        + (s["layers"] - s["dense_layers"]) * router_params(config) \
        + s["d"]


def held_parameters(config):
    """Every parameter of the file: layers, all experts, and the table once
    (the head is tied to it)."""
    s = _sizes(config)
    return _outside_experts(config) \
        + (s["layers"] - s["dense_layers"]) * s["experts"] \
        * expert_params(config) + s["d"] * s["vocab"]


def kv_bytes_a_token(config):
    """Bytes of keys and values one position takes in the attention layers,
    at the bytes the cell stores them in (`cache_dtype`, else `dtype`)."""
    s = _sizes(config)
    wide = config.get("cache_dtype", config["dtype"]) == "float32"
    return s["attention_layers"] * 2 * s["groups"] * s["head"] \
        * (4 if wide else 2)


def tail_bytes_a_row(config):
    """Bytes of convolution tail one request takes in the convolution
    layers, whatever its position: `K - 1` positions of the hidden size."""
    s = _sizes(config)
    wide = config.get("cache_dtype", config["dtype"]) == "float32"
    return s["conv_layers"] * (s["kernel"] - 1) * s["d"] * (4 if wide else 2)


def token_product_flops(config):
    """FLOPs of the products with weights one token needs in all layers
    (its `num_experts_per_tok` experts a layer among them) and of its
    convolutions' taps and gates, without head and attention's products of
    activations."""
    s = _sizes(config)
    expert_layers = s["layers"] - s["dense_layers"]
    products = s["conv_layers"] * 4 * s["d"] * s["d"] \
        + s["attention_layers"] * (attention_params(config) - 2 * s["head"]) \
        + s["dense_layers"] * dense_ffn_params(config) \
        + expert_layers * (s["d"] * s["experts"]
                           + s["per_tok"] * expert_params(config))
    return 2 * products + s["conv_layers"] * (2 * s["kernel"] + 2) * s["d"]


def pair_flops(config):
    """q.k and p.v of one query and one key in all heads of one layer."""
    s = _sizes(config)
    return 4 * s["heads"] * s["head"]


def weight_bytes(config, experts_touched, value_bytes=2):
    """Bytes of weights one pass over all layers reads with
    `experts_touched` distinct experts a layer, and the table as the head."""
    s = _sizes(config)
    return value_bytes * (
        _outside_experts(config)
        + (s["layers"] - s["dense_layers"]) * experts_touched
        * expert_params(config) + s["d"] * s["vocab"])


def prefill_flops(config, rows, prompt_len):
    s = _sizes(config)
    pairs = prompt_len * (prompt_len + 1) // 2
    return rows * (prompt_len * token_product_flops(config)
                   + s["attention_layers"] * pair_flops(config) * pairs
                   + 2 * s["d"] * s["vocab"])


def prefill_bytes(config, rows, prompt_len):
    """Every weight once, the prompt's keys and values and the tails
    written."""
    return weight_bytes(config, _sizes(config)["experts"]) \
        + rows * (prompt_len * kv_bytes_a_token(config)
                  + tail_bytes_a_row(config))


def decode_step_flops(config, rows, live):
    s = _sizes(config)
    return rows * (token_product_flops(config)
                   + s["attention_layers"] * pair_flops(config) * live
                   + 2 * s["d"] * s["vocab"])


def decode_step_bytes(config, rows, live, experts_touched):
    """The weights a step touches, every row's live window read and its
    tails read and written."""
    return weight_bytes(config, experts_touched) \
        + rows * (live * kv_bytes_a_token(config)
                  + 2 * tail_bytes_a_row(config))
