"""What Laguna needs, from the configuration's shapes: FLOPs and bytes of a
prefill and of a decode step. Counted as the mathematics has them (every
token through its `num_experts_per_tok` experts and the shared one and no
padded row; a causal pair of positions once in a full layer, a pair inside
the window once in a sliding layer, `4 x heads x head_dim` FLOP each with
the layer's own heads; each touched expert's weights once a step; keys and
values at the bytes the cell stores them in, a sliding layer's no further
back than its window), not as any program executes them, so a share of a
peak built on these cannot pass 100%."""


def _sizes(config):
    layers = config["num_hidden_layers"]
    types = config["layer_types"][:layers]
    heads = config["num_attention_heads_per_layer"][:layers]
    dense = sum(kind == "dense"
                for kind in config["mlp_layer_types"][:layers])
    return {
        "d": config["hidden_size"], "head": config["head_dim"],
        "groups": config["num_key_value_heads"],
        "window": config["sliding_window"],
        "layers": layers,
        "full": [h for h, kind in zip(heads, types)
                 if kind == "full_attention"],
        "sliding": [h for h, kind in zip(heads, types)
                    if kind == "sliding_attention"],
        "dense_layers": dense, "expert_layers": layers - dense,
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["shared_expert_intermediate_size"],
        "experts": config["num_experts"],
        "per_tok": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
        "value_bytes": 4 if config.get("cache_dtype", config["dtype"])
        == "float32" else 2,
    }


def attention_params(config, heads):
    """One attention layer of `heads` query heads: q, o, the gate a head,
    k, v and the two head norms."""
    s = _sizes(config)
    return 2 * s["d"] * heads * s["head"] + s["d"] * heads \
        + 2 * s["d"] * s["groups"] * s["head"] + 2 * s["head"]


def dense_ffn_params(config):
    s = _sizes(config)
    return 3 * s["d"] * s["dense_width"]


def expert_params(config):
    """One routed expert: gate, up and down."""
    s = _sizes(config)
    return 3 * s["d"] * s["expert_width"]


def shared_params(config):
    """The shared expert and its gate."""
    s = _sizes(config)
    return 3 * s["d"] * s["shared_width"] + s["d"]


def router_params(config):
    s = _sizes(config)
    return s["d"] * s["experts"]


def _outside_experts(config):
    """Every parameter of the layers but the routed experts' own and the
    two tables."""
    s = _sizes(config)
    return sum(attention_params(config, h) for h in s["full"] + s["sliding"]) \
        + s["layers"] * 2 * s["d"] \
        + s["dense_layers"] * dense_ffn_params(config) \
        + s["expert_layers"] * (router_params(config)
                                + shared_params(config)) \
        + s["d"]


def held_parameters(config):
    """Every parameter of the file: layers, all experts, embedding and
    head."""
    s = _sizes(config)
    return _outside_experts(config) \
        + s["expert_layers"] * s["experts"] * expert_params(config) \
        + 2 * s["d"] * s["vocab"]


def kv_bytes_a_token(config):
    """Bytes of keys and values one position takes in the FULL layers."""
    s = _sizes(config)
    return len(s["full"]) * 2 * s["groups"] * s["head"] * s["value_bytes"]


def ring_bytes_a_row(config, max_len=None):
    """Bytes of keys and values one request takes in the sliding layers,
    whatever its position: a window of positions (no more than `max_len`)."""
    s = _sizes(config)
    kept = s["window"] if max_len is None else min(s["window"], max_len)
    return len(s["sliding"]) * kept * 2 * s["groups"] * s["head"] \
        * s["value_bytes"]


def token_product_flops(config):
    """FLOPs of the products with weights one token needs in all layers
    (its `num_experts_per_tok` experts and the shared one among them),
    without head and attention's products of activations."""
    s = _sizes(config)
    products = sum(attention_params(config, h) - 2 * s["head"]
                   for h in s["full"] + s["sliding"]) \
        + s["dense_layers"] * dense_ffn_params(config) \
        + s["expert_layers"] * (router_params(config) + shared_params(config)
                                + s["per_tok"] * expert_params(config))
    return 2 * products


def pair_flops(config, heads):
    """q.k and p.v of one query and one key in all `heads` of one layer."""
    return 4 * heads * _sizes(config)["head"]


def window_pairs(config, first, count):
    """Sum over the `count` queries from position `first` of the positions
    a sliding layer lets each attend: min(t + 1, window)."""
    window = _sizes(config)["window"]
    ramp = max(0, min(first + count, window) - first)      # t + 1 each
    return ramp * (2 * first + ramp + 1) // 2 + (count - ramp) * window


def attention_flops(config, first, count):
    """The attention's products of activations for `count` queries from
    position `first`, over all layers: a causal pair once in a full layer,
    a pair inside the window once in a sliding one."""
    s = _sizes(config)
    causal = count * first + count * (count + 1) // 2
    return sum(pair_flops(config, h) for h in s["full"]) * causal \
        + sum(pair_flops(config, h) for h in s["sliding"]) \
        * window_pairs(config, first, count)


def weight_bytes(config, experts_touched, value_bytes=2):
    """Bytes of weights one pass over all layers reads with
    `experts_touched` distinct experts a layer, and the head."""
    s = _sizes(config)
    return value_bytes * (
        _outside_experts(config)
        + s["expert_layers"] * experts_touched * expert_params(config)
        + s["d"] * s["vocab"])


def prefill_flops(config, rows, prompt_len):
    s = _sizes(config)
    return rows * (prompt_len * token_product_flops(config)
                   + attention_flops(config, 0, prompt_len)
                   + 2 * s["d"] * s["vocab"])


def prefill_bytes(config, rows, prompt_len):
    """Every weight once, the prompt's keys and values written in the full
    layers and the rings written once over."""
    return weight_bytes(config, _sizes(config)["experts"]) \
        + rows * (prompt_len * kv_bytes_a_token(config)
                  + ring_bytes_a_row(config, prompt_len))


def decode_step_flops(config, rows, live):
    s = _sizes(config)
    return rows * (token_product_flops(config)
                   + attention_flops(config, int(live), 1)
                   + 2 * s["d"] * s["vocab"])


def decode_step_bytes(config, rows, live, experts_touched):
    """The weights a step touches, every row's live window read in the full
    layers and its rings read in the sliding ones."""
    return weight_bytes(config, experts_touched) \
        + rows * (live * kv_bytes_a_token(config)
                  + ring_bytes_a_row(config, int(live)))
