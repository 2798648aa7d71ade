"""What MiniCPM-SALA needs, from the configuration's shapes: FLOPs and bytes
of a prefill and of a decode step. Counted as the mathematics has them (a
sparse layer's query over the positions it KEEPS and the pooled keys it
scores, never the window a program may read to find them; a span's
lightning attention in the chunked form, a step's as the recurrence, which
reads the state and writes it; every weight once a prefill, whatever the
number of spans), not as any program executes them, so a share of a peak
built on these cannot pass 100%."""

CHUNK = 128     # positions a chunk of the chunked form


def _sizes(config):
    layers = config["num_hidden_layers"]
    mixers = config["mixer_types"][:layers]
    sparse = sum(kind == "minicpm4" for kind in mixers)
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "heads": config["num_attention_heads"],
        "groups": config["num_key_value_heads"], "dh": config["head_dim"],
        "lh": config["lightning_nh"], "lkv": config["lightning_nkv"],
        "ldh": config["lightning_head_dim"], "layers": layers,
        "sparse": sparse, "lightning": layers - sparse,
        "vocab": config["vocab_size"], **config["sparse_config"]}


def sparse_mixer_params(config):
    """One minicpm4 mixer: q, k, v, o, the output gate and two norms."""
    s = _sizes(config)
    return 3 * s["d"] * s["heads"] * s["dh"] \
        + 2 * s["d"] * s["groups"] * s["dh"] + 2 * s["dh"]


def lightning_mixer_params(config):
    """One lightning mixer: q, o, the output gate, k, v, two norms a head
    and the output norm."""
    s = _sizes(config)
    return 3 * s["d"] * s["lh"] * s["ldh"] \
        + 2 * s["d"] * s["lkv"] * s["ldh"] + 2 * s["ldh"] \
        + s["lh"] * s["ldh"]


def layer_fixed_params(config):
    """Of a layer, beside its mixer: the dense FFN and two norms."""
    s = _sizes(config)
    return 3 * s["d"] * s["f"] + 2 * s["d"]


def held_parameters(config):
    """Every parameter the chip holds: layers, embedding, norm and head."""
    s = _sizes(config)
    return s["sparse"] * sparse_mixer_params(config) \
        + s["lightning"] * lightning_mixer_params(config) \
        + s["layers"] * layer_fixed_params(config) \
        + 2 * s["d"] * s["vocab"] + s["d"]


def _wide(config):
    return 4 if config.get("cache_dtype", config["dtype"]) == "float32" else 2


def state_bytes_a_row(config):
    """Bytes of state one request keeps in all the lightning layers."""
    s = _sizes(config)
    return s["lightning"] * _wide(config) * s["lh"] * s["ldh"] * s["ldh"]


def kv_bytes_a_token(config):
    """Bytes one position takes in the sparse layers: keys, values and its
    share of a pooled key."""
    s = _sizes(config)
    return s["sparse"] * s["groups"] * s["dh"] * _wide(config) \
        * (2 + 1 / s["kernel_stride"])


def token_product_flops(config):
    """FLOPs of the products with weights one token needs in all layers,
    without the head and the products of two activations."""
    s = _sizes(config)
    return 2 * (s["sparse"] * sparse_mixer_params(config)
                + s["lightning"] * lightning_mixer_params(config)
                + s["layers"] * layer_fixed_params(config))


def kept_positions(config, t):
    """Positions the query at `t` attends in a sparse layer: all `t + 1`
    below dense_len or while every block is kept, else the kept blocks'
    (the query's own block up to `t`)."""
    s = _sizes(config)
    block = s["block_size"]
    slots = s["init_blocks"] + s["window_size"] // block + s["topk"]
    if t < s["dense_len"] or t // block + 1 <= slots:
        return t + 1
    return (slots - 1) * block + t % block + 1


def kernels_scored(config, t):
    """Pooled keys the query at `t` scores (none below dense_len)."""
    s = _sizes(config)
    if t < s["dense_len"]:
        return 0
    return (t + 1 - s["kernel_size"]) // s["kernel_stride"] + 1


def sparse_query_flops(config, t):
    """One query at `t` in one sparse layer, all heads: q.Kbar over the
    kernels it scores, q.k and p.v over the positions it keeps."""
    s = _sizes(config)
    return s["heads"] * s["dh"] * (2 * kernels_scored(config, t)
                                   + 4 * kept_positions(config, t))


def sparse_query_bytes(config, t):
    """What one query at `t` must read of one sparse layer's cache: the
    pooled keys it scores and the keys and values of what it keeps."""
    s = _sizes(config)
    return s["groups"] * s["dh"] * _wide(config) \
        * (kernels_scored(config, t) + 2 * kept_positions(config, t))


def chunk_flops(config, chunk=CHUNK):
    """One head's products in one chunk of the chunked form: Q K^T and the
    decayed scores times V (2 C^2 Dh each), Q S and K^T V into the state (2
    C Dh^2 each)."""
    dh = _sizes(config)["ldh"]
    return 4 * chunk * chunk * dh + 4 * chunk * dh * dh


def recurrence_flops(config):
    """One head's one position of the recurrence: the decay, k v^T and its
    sum into S (Dh^2 each), q S (2 Dh^2)."""
    dh = _sizes(config)["ldh"]
    return 5 * dh * dh


def weight_bytes(config, value_bytes=2):
    """Bytes of weights one pass over all layers reads, and the head."""
    s = _sizes(config)
    return value_bytes * (held_parameters(config) - s["d"] * s["vocab"])


def prefill_flops(config, rows, prompt_len):
    s = _sizes(config)
    chunks = -(-prompt_len // CHUNK)
    attended = sum(sparse_query_flops(config, t) for t in range(prompt_len))
    return rows * (prompt_len * token_product_flops(config)
                   + s["lightning"] * s["lh"] * chunks * chunk_flops(config)
                   + s["sparse"] * attended + 2 * s["d"] * s["vocab"])


def prefill_bytes(config, rows, prompt_len):
    """Every held weight once, the prompt's keys, values and pooled keys
    written, and the state written once."""
    return weight_bytes(config) \
        + rows * (prompt_len * kv_bytes_a_token(config)
                  + state_bytes_a_row(config))


def decode_step_flops(config, rows, live):
    s = _sizes(config)
    return rows * (token_product_flops(config)
                   + s["lightning"] * s["lh"] * recurrence_flops(config)
                   + s["sparse"] * sparse_query_flops(config, int(live))
                   + 2 * s["d"] * s["vocab"])


def decode_step_bytes(config, rows, live):
    """The weights, what each row's query must read of the sparse layers,
    and its state read and written."""
    s = _sizes(config)
    return weight_bytes(config) \
        + rows * (s["sparse"] * sparse_query_bytes(config, int(live))
                  + 2 * state_bytes_a_row(config))
