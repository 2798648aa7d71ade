"""What Brumby (power retention) needs, from the configuration's shapes:
FLOPs and bytes of a prefill and of a decode step. Counted as the
mathematics has them (a span's retention layers in the chunked form at the
configuration's chunk over the 8,256 DISTINCT second-power products of a
128-wide key, a decode step's as the recurrence, which reads the state ONCE
and writes it ONCE at the bytes the cell's leaf stores; every weight once a
step and once a prefill, whatever the number of spans, the head's table
once: the embedding reads a row a token; no key or value is kept, so
nothing is attended), not as any program executes them, so a share of a
peak built on these cannot pass 100%: a program that reads the state twice
a step, or copies it, reads a lower share."""


def _sizes(config):
    hd = config["head_dim"]
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "groups": config["num_key_value_heads"], "hd": hd,
        # the symmetric second power without its duplicates, and as the
        # program's leaf lays it out: hd / 2 + 1 whole rows of hd lanes
        "distinct": hd * (hd + 1) // 2, "stored": (hd // 2 + 1) * hd,
        # the chunk the configuration assumes (`assumed.chunk`)
        "chunk": 128,
        "layers": config["num_hidden_layers"],
        "width": config["intermediate_size"], "vocab": config["vocab_size"],
    }


def mixer_params(config):
    """One retention mixer: q, o; k, v; the gate's projection; q and k
    norms."""
    s = _sizes(config)
    return 2 * s["d"] * s["heads"] * s["hd"] \
        + 2 * s["d"] * s["groups"] * s["hd"] + s["d"] * s["groups"] \
        + 2 * s["hd"]


def swiglu_params(config):
    s = _sizes(config)
    return 3 * s["d"] * s["width"]


def held_parameters(config):
    """Every parameter held: the layers (two norms each), the two tables,
    the final norm."""
    s = _sizes(config)
    return s["layers"] * (mixer_params(config) + swiglu_params(config)
                          + 2 * s["d"]) \
        + 2 * s["d"] * s["vocab"] + s["d"]


def _wide(config):
    return 4 if config.get("cache_dtype", config["dtype"]) == "float32" else 2


def layer_state_bytes_a_row(config):
    """Bytes of ONE layer's state one request keeps, as the leaf stores it
    (the sum of keys, 0.8% of it, is not counted)."""
    s = _sizes(config)
    return _wide(config) * s["groups"] * s["hd"] * s["stored"]


def state_bytes_a_row(config):
    """Bytes of state and sums of keys one request keeps in all layers,
    whatever its length."""
    s = _sizes(config)
    return s["layers"] * (layer_state_bytes_a_row(config)
                          + _wide(config) * s["groups"] * s["stored"])


def token_product_flops(config):
    """FLOPs of the products with weights one token needs in all layers,
    without the head and the retention's products of activations."""
    s = _sizes(config)
    return 2 * s["layers"] * (mixer_params(config) - 2 * s["hd"]
                              + swiglu_params(config))


def chunk_flops(config):
    """One layer's products of two activations in one chunk of the chunked
    form: a query head q k^T and the weights times v (2 C^2 hd each) and the
    expanded queries over the state (2 C F hd); a KV head the state's update
    (2 C F hd)."""
    s = _sizes(config)
    c, f, hd = s["chunk"], s["distinct"], s["hd"]
    return s["heads"] * (4 * c * c * hd + 2 * c * f * hd) \
        + s["groups"] * 2 * c * f * hd


def recurrence_flops(config):
    """One layer's one position of the recurrence: a KV head the decay (hd F)
    and v phi(k)^T into S (2 hd F), a query head S phi(q) (2 hd F)."""
    s = _sizes(config)
    return (3 * s["groups"] + 2 * s["heads"]) * s["hd"] * s["distinct"]


def weight_bytes(config, value_bytes=2):
    """Bytes of weights one pass over all layers and the head reads: the
    embedding's table is read a row a token, not whole."""
    s = _sizes(config)
    return value_bytes * (held_parameters(config) - s["d"] * s["vocab"])


def prefill_flops(config, rows, prompt_len):
    s = _sizes(config)
    chunks = -(-prompt_len // s["chunk"])
    return rows * (prompt_len * token_product_flops(config)
                   + s["layers"] * chunks * chunk_flops(config)
                   + 2 * s["d"] * s["vocab"])


def prefill_bytes(config, rows):
    """Every weight once and the state written once, whatever the prompt."""
    return weight_bytes(config) + rows * state_bytes_a_row(config)


def decode_step_flops(config, rows):
    s = _sizes(config)
    return rows * (token_product_flops(config)
                   + s["layers"] * recurrence_flops(config)
                   + 2 * s["d"] * s["vocab"])


def decode_step_bytes(config, rows):
    """Every weight, and each row's state read once and written once."""
    return weight_bytes(config) + 2 * rows * state_bytes_a_row(config)
