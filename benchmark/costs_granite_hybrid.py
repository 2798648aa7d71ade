"""What Granite 4.0-H needs, from the configuration's shapes: FLOPs and
bytes of a prefill and of a decode step. Counted as the mathematics has
them (a span's Mamba-2 layers in the chunked form at the configuration's
chunk, a decode step's as the recurrence, which reads the state ONCE and
writes it ONCE at the bytes the cell stores it in; the attention over the
live positions alone; every weight once a step and once a prefill, whatever
the number of spans, the tied table once, as the head: the embedding reads a
row a token), not as any program executes them, so a share of a peak built
on these cannot pass 100%: a program that reads the state twice a step, or
copies it, reads a lower share."""


def _sizes(config):
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "groups": config["num_key_value_heads"],
        "dh": config["hidden_size"] // config["num_attention_heads"],
        "ssm_heads": heads, "p": p, "n": n, "ssm_groups": groups,
        "inner": heads * p, "channels": heads * p + 2 * groups * n,
        "conv": config["mamba_d_conv"], "chunk": config["mamba_chunk_size"],
        "mamba": kinds.count("mamba"), "attn": kinds.count("attention"),
        "layers": len(kinds), "width": config["shared_intermediate_size"],
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


def swiglu_params(config):
    """A block's feed-forward part: the fused gate and up, and down."""
    s = _sizes(config)
    return 3 * s["d"] * s["width"]


def held_parameters(config):
    """Every parameter of the model: blocks (two norms each), the tied
    table once, the final norm."""
    s = _sizes(config)
    return s["mamba"] * mamba_params(config) \
        + s["attn"] * attention_params(config) \
        + s["layers"] * (swiglu_params(config) + 2 * s["d"]) \
        + s["d"] * s["vocab"] + s["d"]


def _wide(config):
    return 4 if config.get("cache_dtype", config["dtype"]) == "float32" else 2


def layer_state_bytes_a_row(config):
    """Bytes of ONE Mamba-2 layer's state one request keeps."""
    s = _sizes(config)
    return _wide(config) * s["ssm_heads"] * s["p"] * s["n"]


def state_bytes_a_row(config):
    """Bytes of state and convolution inputs one request keeps in all the
    Mamba-2 layers, whatever its length."""
    s = _sizes(config)
    return s["mamba"] * (layer_state_bytes_a_row(config) + _wide(config)
                         * (s["conv"] - 1) * s["channels"])


def kv_bytes_a_token(config):
    """Bytes of keys and values one position takes in the attention
    layers."""
    s = _sizes(config)
    return s["attn"] * 2 * s["groups"] * s["dh"] * _wide(config)


def token_product_flops(config):
    """FLOPs of the products with weights one token needs in all blocks,
    without the head, the recurrence and the attention's products of
    activations."""
    s = _sizes(config)
    return 2 * (s["mamba"] * mamba_params(config)
                + s["attn"] * attention_params(config)
                + s["layers"] * swiglu_params(config))


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


def weight_bytes(config, value_bytes=2):
    """Bytes of weights one pass over all blocks and the head reads: the
    table once."""
    return value_bytes * held_parameters(config)


def prefill_flops(config, rows, prompt_len):
    s = _sizes(config)
    pairs = prompt_len * (prompt_len + 1) // 2
    chunks = -(-prompt_len // s["chunk"])
    return rows * (prompt_len * token_product_flops(config)
                   + s["mamba"] * chunks * chunk_flops(config)
                   + s["attn"] * attention_pair_flops(config) * pairs
                   + 2 * s["d"] * s["vocab"])


def prefill_bytes(config, rows, prompt_len):
    """Every weight once, the prompt's keys and values written, and the
    state written once."""
    return weight_bytes(config) \
        + rows * (prompt_len * kv_bytes_a_token(config)
                  + state_bytes_a_row(config))


def decode_step_flops(config, rows, live):
    s = _sizes(config)
    return rows * (token_product_flops(config)
                   + s["mamba"] * recurrence_flops(config)
                   + s["attn"] * attention_pair_flops(config) * live
                   + 2 * s["d"] * s["vocab"])


def decode_step_bytes(config, rows, live):
    """Every weight, each row's live keys and values read, and its state
    read once and written once."""
    return weight_bytes(config) \
        + rows * (live * kv_bytes_a_token(config)
                  + 2 * state_bytes_a_row(config))
