"""What Qwen3-Next needs, from the configuration's shapes: FLOPs and bytes
of a prefill and of a decode step. Counted as the mathematics has them (a
span's delta rule in the chunked form, its products a chunk; a decode
step's as the recurrence, which reads the state and writes it; the gated
attention over the live positions alone; the held experts a token as routed;
each touched expert's weights once a step; every weight once a prefill,
whatever the number of spans), not as any program executes them, so a share
of a peak built on these cannot pass 100%."""


CHUNK = 64      # positions a chunk of the chunked form


def _sizes(config):
    layers = config["num_hidden_layers"]
    full = layers // config["full_attention_interval"]
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "groups": config["num_key_value_heads"], "dh": config["head_dim"],
        "hk": config["linear_num_key_heads"],
        "hv": config["linear_num_value_heads"],
        "dk": config["linear_key_head_dim"],
        "dv": config["linear_value_head_dim"],
        "conv": config["linear_conv_kernel_dim"],
        "layers": layers, "full": full, "linear": layers - full,
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["shared_expert_intermediate_size"],
        "held": config["num_experts"],
        "routed": config.get("published", {}).get(
            "num_experts", config["num_experts"]),
        "per_tok": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
    }


def conv_channels(config):
    s = _sizes(config)
    return 2 * s["hk"] * s["dk"] + s["hv"] * s["dv"]


def linear_mixer_params(config):
    """One Gated DeltaNet mixer: in_proj_qkvz, in_proj_ba, conv1d, out_proj,
    dt_bias, A_log and the output norm."""
    s = _sizes(config)
    return s["d"] * (conv_channels(config) + s["hv"] * s["dv"]) \
        + s["d"] * 2 * s["hv"] + conv_channels(config) * s["conv"] \
        + s["hv"] * s["dv"] * s["d"] + 2 * s["hv"] + s["dv"]


def attention_params(config):
    """One gated attention: q (query and gate), k, v, o and two norms."""
    s = _sizes(config)
    return s["d"] * 2 * s["heads"] * s["dh"] \
        + 2 * s["d"] * s["groups"] * s["dh"] \
        + s["heads"] * s["dh"] * s["d"] + 2 * s["dh"]


def expert_params(config):
    """One routed expert: gate, up and down."""
    s = _sizes(config)
    return 3 * s["d"] * s["expert_width"]


def layer_fixed_params(config):
    """Of a layer, beside its mixer, what every token multiplies: the
    router over all the published experts, the shared expert, its gate and
    the layer's two norms."""
    s = _sizes(config)
    return s["d"] * s["routed"] + 3 * s["d"] * s["shared_width"] \
        + s["d"] + 2 * s["d"]


def held_parameters(config):
    """Every parameter the chip holds: layers, embedding, norm and head."""
    s = _sizes(config)
    return s["linear"] * linear_mixer_params(config) \
        + s["full"] * attention_params(config) \
        + s["layers"] * (layer_fixed_params(config)
                         + s["held"] * expert_params(config)) \
        + 2 * s["d"] * s["vocab"] + s["d"]


def _wide(config):
    return 4 if config.get("cache_dtype", config["dtype"]) == "float32" else 2


def state_bytes_a_row(config):
    """Bytes of state and convolution inputs one request keeps in all the
    linear layers, whatever its length."""
    s = _sizes(config)
    return s["linear"] * _wide(config) * (
        s["hv"] * s["dk"] * s["dv"]
        + (s["conv"] - 1) * conv_channels(config))


def kv_bytes_a_token(config):
    """Bytes of keys and values one position takes in the full layers."""
    s = _sizes(config)
    return s["full"] * 2 * s["groups"] * s["dh"] * _wide(config)


def expected_held_a_token(config):
    """Assignments a token makes to held experts in one layer if the router
    spreads its choices evenly."""
    s = _sizes(config)
    return s["per_tok"] * s["held"] / s["routed"]


def token_product_flops(config, held_a_token=None):
    """FLOPs of the products with weights one token needs in all layers
    (its `held_a_token` held experts a layer among them), without the head,
    the delta rule and the attention's products of activations."""
    s = _sizes(config)
    if held_a_token is None:
        held_a_token = expected_held_a_token(config)
    return 2 * (s["linear"] * linear_mixer_params(config)
                + s["full"] * attention_params(config)
                + s["layers"] * (layer_fixed_params(config)
                                 + held_a_token * expert_params(config)))


def chunk_flops(config, chunk=CHUNK):
    """One value head's products in one chunk of the chunked form: K K^T
    and Q K^T (2 C^2 Dk each), the unit lower triangular inverse by forward
    substitution (2 C^3 / 3), the inverse times beta V (2 C^2 Dv) and times
    beta exp(gamma) K (2 C^2 Dk), W S_0 and Q S_0 (2 C Dk Dv each), the
    within-chunk scores times D (2 C^2 Dv) and K^T D into the state (2 C Dk
    Dv)."""
    s = _sizes(config)
    c, dk, dv = chunk, s["dk"], s["dv"]
    return 6 * c * c * dk + 4 * c * c * dv + 6 * c * dk * dv \
        + 2 * c ** 3 // 3


def recurrence_flops(config):
    """One value head's one position of the recurrence: the decay (Dk Dv),
    S^T k, k d^T into S and S^T q (2 Dk Dv each)."""
    s = _sizes(config)
    return 7 * s["dk"] * s["dv"]


def attention_pair_flops(config):
    """q.k and p.v of one query and one key in all heads."""
    s = _sizes(config)
    return 4 * s["heads"] * s["dh"]


def weight_bytes(config, experts_touched, value_bytes=2):
    """Bytes of weights one pass over all layers reads with
    `experts_touched` distinct held experts a layer, and the head."""
    s = _sizes(config)
    return value_bytes * (
        s["linear"] * linear_mixer_params(config)
        + s["full"] * attention_params(config)
        + s["layers"] * (layer_fixed_params(config)
                         + experts_touched * expert_params(config))
        + s["d"] * s["vocab"] + s["d"])


def prefill_flops(config, rows, prompt_len, held_a_token=None):
    s = _sizes(config)
    pairs = prompt_len * (prompt_len + 1) // 2
    chunks = -(-prompt_len // CHUNK)
    return rows * (prompt_len * token_product_flops(config, held_a_token)
                   + s["linear"] * s["hv"] * chunks * chunk_flops(config)
                   + s["full"] * attention_pair_flops(config) * pairs
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
                   + s["linear"] * s["hv"] * recurrence_flops(config)
                   + s["full"] * attention_pair_flops(config) * live
                   + 2 * s["d"] * s["vocab"])


def decode_step_bytes(config, rows, live, experts_touched):
    """The touched weights, each row's live keys and values read, and its
    state read and written."""
    return weight_bytes(config, experts_touched) \
        + rows * (live * kv_bytes_a_token(config)
                  + 2 * state_bytes_a_row(config))
