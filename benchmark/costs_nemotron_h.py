"""What Nemotron-H needs, from the configuration's shapes: FLOPs and bytes
of a prefill and of a decode step. Counted as the mathematics has them (a
span's Mamba-2 layers in the chunked form at the configuration's chunk, a
decode step's as the recurrence, which reads the state ONCE and writes it
ONCE; the attention over the live positions alone; the held experts a token
as routed, in the latent's width; each touched expert's weights once a step;
every weight once a prefill, whatever the number of spans), not as any
program executes them, so a share of a peak built on these cannot pass
100%: a program that reads the state twice a step, or copies it, reads a
lower share."""


def _sizes(config):
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "groups": config["num_key_value_heads"], "dh": config["head_dim"],
        "ssm_heads": heads, "p": p, "n": n, "ssm_groups": groups,
        "inner": heads * p, "channels": heads * p + 2 * groups * n,
        "conv": config["conv_kernel"], "chunk": config["chunk_size"],
        "mamba": pattern.count("M"), "attn": pattern.count("*"),
        "experts": pattern.count("E"), "layers": len(pattern),
        "latent": config["moe_latent_size"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["moe_shared_expert_intermediate_size"],
        "held": config["n_routed_experts"],
        "routed": config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"]),
        "per_tok": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
    }


def mamba_params(config):
    """One Mamba-2 mixer: in_proj, conv1d and its bias, A_log, D, dt_bias,
    the gated norm and out_proj."""
    s = _sizes(config)
    return s["d"] * (s["inner"] + s["channels"] + s["ssm_heads"]) \
        + s["channels"] * (s["conv"] + 1) + 3 * s["ssm_heads"] \
        + s["inner"] + s["inner"] * s["d"]


def attention_params(config):
    """One attention: q, o and k, v."""
    s = _sizes(config)
    return 2 * s["d"] * s["heads"] * s["dh"] \
        + 2 * s["d"] * s["groups"] * s["dh"]


def expert_params(config):
    """One routed expert: up and down, in the latent's width."""
    s = _sizes(config)
    return 2 * s["latent"] * s["expert_width"]


def expert_layer_fixed_params(config):
    """Of an expert layer what every token multiplies: the router over all
    the published experts and its bias, the two latent projections, the
    shared expert."""
    s = _sizes(config)
    return s["d"] * s["routed"] + s["routed"] + 2 * s["d"] * s["latent"] \
        + 2 * s["d"] * s["shared_width"]


def held_parameters(config):
    """Every parameter the chip holds: layers (a norm each), embedding,
    final norm and head."""
    s = _sizes(config)
    return s["mamba"] * mamba_params(config) \
        + s["attn"] * attention_params(config) \
        + s["experts"] * (expert_layer_fixed_params(config)
                          + s["held"] * expert_params(config)) \
        + s["layers"] * s["d"] + 2 * s["d"] * s["vocab"] + s["d"]


def _wide(config):
    return 4 if config.get("cache_dtype", config["dtype"]) == "float32" else 2


def state_bytes_a_row(config):
    """Bytes of state and convolution inputs one request keeps in all the
    Mamba-2 layers, whatever its length."""
    s = _sizes(config)
    return s["mamba"] * _wide(config) * (
        s["ssm_heads"] * s["p"] * s["n"] + (s["conv"] - 1) * s["channels"])


def kv_bytes_a_token(config):
    """Bytes of keys and values one position takes in the attention
    layers."""
    s = _sizes(config)
    return s["attn"] * 2 * s["groups"] * s["dh"] * _wide(config)


def expected_held_a_token(config):
    """Assignments a token makes to held experts in one layer if the router
    spreads its choices evenly."""
    s = _sizes(config)
    return s["per_tok"] * s["held"] / s["routed"]


def token_product_flops(config, held_a_token=None):
    """FLOPs of the products with weights one token needs in all layers
    (its `held_a_token` held experts a layer among them), without the head,
    the recurrence and the attention's products of activations."""
    s = _sizes(config)
    if held_a_token is None:
        held_a_token = expected_held_a_token(config)
    return 2 * (s["mamba"] * mamba_params(config)
                + s["attn"] * attention_params(config)
                + s["experts"] * (expert_layer_fixed_params(config)
                                  + held_a_token * expert_params(config)))


def chunk_flops(config):
    """One layer's products of two activations in one chunk of the chunked
    form: C B^T a group (2 C^2 N), and a head the scores times dt x (2 C^2
    P), C S_prev and the update of the state (2 C P N each)."""
    s = _sizes(config)
    c = s["chunk"]
    return s["ssm_groups"] * 2 * c * c * s["n"] \
        + s["ssm_heads"] * (2 * c * c * s["p"] + 4 * c * s["p"] * s["n"])


def recurrence_flops(config):
    """One layer's one position of the recurrence: the decay (P N a head),
    dt x B^T into S and S C (2 P N each)."""
    s = _sizes(config)
    return 5 * s["ssm_heads"] * s["p"] * s["n"]


def attention_pair_flops(config):
    """q.k and p.v of one query and one key in all heads."""
    s = _sizes(config)
    return 4 * s["heads"] * s["dh"]


def weight_bytes(config, experts_touched, value_bytes=2):
    """Bytes of weights one pass over all layers reads with
    `experts_touched` distinct held experts a layer, and the head."""
    s = _sizes(config)
    return value_bytes * (
        s["mamba"] * mamba_params(config)
        + s["attn"] * attention_params(config)
        + s["experts"] * (expert_layer_fixed_params(config)
                          + experts_touched * expert_params(config))
        + s["layers"] * s["d"] + s["d"] * s["vocab"] + s["d"])


def prefill_flops(config, rows, prompt_len, held_a_token=None):
    s = _sizes(config)
    pairs = prompt_len * (prompt_len + 1) // 2
    chunks = -(-prompt_len // s["chunk"])
    return rows * (prompt_len * token_product_flops(config, held_a_token)
                   + s["mamba"] * chunks * chunk_flops(config)
                   + s["attn"] * attention_pair_flops(config) * pairs
                   + 2 * s["d"] * s["vocab"])


def prefill_bytes(config, rows, prompt_len):
    """Every held weight once, the prompt's keys and values written, and
    the state written once."""
    return weight_bytes(config, _sizes(config)["held"]) \
        + rows * (prompt_len * kv_bytes_a_token(config)
                  + state_bytes_a_row(config))


def decode_step_flops(config, rows, live, held_a_token=None):
    s = _sizes(config)
    return rows * (token_product_flops(config, held_a_token)
                   + s["mamba"] * recurrence_flops(config)
                   + s["attn"] * attention_pair_flops(config) * live
                   + 2 * s["d"] * s["vocab"])


def decode_step_bytes(config, rows, live, experts_touched):
    """The touched weights, each row's live keys and values read, and its
    state read once and written once."""
    return weight_bytes(config, experts_touched) \
        + rows * (live * kv_bytes_a_token(config)
                  + 2 * state_bytes_a_row(config))
