"""What Mellum needs, from the configuration's shapes: FLOPs and bytes of a
span of a prompt pass and of a decode step of rows that stand each at its
own position. Counted as the mathematics has them (every live token through
its `num_experts_per_tok` experts and no padded or dead row; a pair of a
query and a position below it once in a full layer, `4 x heads x head_dim`
FLOP; each touched expert's weights once a call; keys and values at the
bytes the cell stores them in, a window layer's no further back than its
window), not as any program executes them, so a share of a peak built on
these cannot pass 100%. The pairs inside a window layer's window and inside
a span's own rows are left out (5% of a prompt's products with weights at
the cell's lengths): that errs low."""


def _sizes(config):
    layers = config["num_hidden_layers"]
    types = config["layer_types"][:layers]
    return {
        "d": config["hidden_size"], "head": config["head_dim"],
        "heads": config["num_attention_heads"],
        "groups": config["num_key_value_heads"],
        "window": config["sliding_window"], "layers": layers,
        "full": sum(kind == "full_attention" for kind in types),
        "sliding": sum(kind == "sliding_attention" for kind in types),
        "expert_width": config["moe_intermediate_size"],
        "experts": config["num_experts"],
        "per_tok": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
        "value_bytes": 4 if config.get("cache_dtype", config["dtype"])
        == "float32" else 2,
    }


def attention_params(config):
    """One attention layer: q, o, k, v and the two head norms."""
    s = _sizes(config)
    return 2 * s["d"] * s["heads"] * s["head"] \
        + 2 * s["d"] * s["groups"] * s["head"] + 2 * s["head"]


def expert_params(config):
    """One routed expert: gate, up and down."""
    s = _sizes(config)
    return 3 * s["d"] * s["expert_width"]


def router_params(config):
    s = _sizes(config)
    return s["d"] * s["experts"]


def _outside_experts(config):
    """Every parameter of the layers but the experts' own and the two
    tables: attention, router, two norms a layer, the final norm."""
    s = _sizes(config)
    return s["layers"] * (attention_params(config) + router_params(config)
                          + 2 * s["d"]) + s["d"]


def held_parameters(config):
    """Every parameter of the file: layers, all experts, embedding and
    head."""
    s = _sizes(config)
    return _outside_experts(config) \
        + s["layers"] * s["experts"] * expert_params(config) \
        + 2 * s["d"] * s["vocab"]


def kv_bytes_a_position(config):
    """Bytes of keys and values one position of one layer takes."""
    s = _sizes(config)
    return 2 * s["groups"] * s["head"] * s["value_bytes"]


def slot_bytes(config, max_len):
    """Bytes one slot of the stage-wide cache takes: rows to `max_len` in
    the full layers, a ring of the window in the others."""
    s = _sizes(config)
    return (s["full"] * max_len + s["sliding"] * min(s["window"], max_len)) \
        * kv_bytes_a_position(config)


def token_product_flops(config):
    """FLOPs of the products with weights one token needs in all layers
    (its `num_experts_per_tok` experts among them), without the head."""
    s = _sizes(config)
    return 2 * s["layers"] * (attention_params(config) - 2 * s["head"]
                              + router_params(config)
                              + s["per_tok"] * expert_params(config))


def pair_flops(config):
    """q.k and p.v of one query and one key in all heads of one layer."""
    s = _sizes(config)
    return 4 * s["heads"] * s["head"]


def head_flops(config):
    s = _sizes(config)
    return 2 * s["d"] * s["vocab"]


def steps_flops(config, rows, positions_below):
    """Decode steps that stepped `rows` live rows in all (a row a token),
    with `positions_below` positions below them summed over those rows:
    products with weights, the head, and the full layers' pairs."""
    s = _sizes(config)
    return rows * (token_product_flops(config) + head_flops(config)) \
        + s["full"] * pair_flops(config) * positions_below


def steps_bytes(config, steps, rows, positions_below, experts_touched):
    """What `steps` decode steps read: everything but the experts and the
    head's table once a step, `experts_touched` experts' matrices (summed
    over the steps' layer calls), each live row's positions below it in the
    full layers and no more than the window in the others."""
    s = _sizes(config)
    per_row_window = min(s["window"], positions_below / max(rows, 1))
    return 2 * (steps * (_outside_experts(config) + s["d"] * s["vocab"])
                + experts_touched * expert_params(config)) \
        + kv_bytes_a_position(config) * (
            s["full"] * positions_below
            + s["sliding"] * rows * per_row_window)


def spans_flops(config, spans, positions, positions_below):
    """Prompt spans, `positions` prompt positions in all and
    `positions_below` cached positions below each span summed over its
    queries: products with weights, one head row a span, the full layers'
    pairs below the span."""
    s = _sizes(config)
    return positions * token_product_flops(config) \
        + spans * head_flops(config) \
        + s["full"] * pair_flops(config) * positions_below


def spans_bytes(config, spans, positions, experts_touched):
    """What the spans read and write: everything but the experts and the
    head's table once a span, the touched experts' matrices, each position's
    keys and values written once in every layer."""
    s = _sizes(config)
    return 2 * (spans * (_outside_experts(config) + s["d"] * s["vocab"])
                + experts_touched * expert_params(config)) \
        + positions * s["layers"] * kv_bytes_a_position(config)
