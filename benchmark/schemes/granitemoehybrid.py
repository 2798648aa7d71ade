"""Granite 4.0-H (`model_type` "granitemoehybrid"; granite-4.0-h-micro, the
dense member): the published state dict as the configuration's
`assumed.checkpoint_keys` has it. `nn.Linear` kernels [out, in], no biases
but the convolution's. Block `i` is what `layer_types[i]` says: "mamba" a
Mamba-2 mixer (`mamba.{in_proj,out_proj}.weight`, `mamba.conv1d.{weight,
bias}`, `mamba.{A_log,D,dt_bias}`, `mamba.norm.weight` over the inner
width), "attention" a plain one (`self_attn.{q,k,v,o}_proj.weight`); every
block has its two norms and `shared_mlp.{input_linear,output_linear}.weight`,
the input one FUSED: gate's rows then up's, [2 x shared_intermediate_size,
hidden]. No `lm_head.weight`: the head is the embedding. A configuration with
routed experts (`num_local_experts` > 0, the line's larger members) is
refused: this scheme writes none.

**The decay**, as `schemes/nemotron_h.py` says why: `A_log` and `dt_bias` are
drawn as Mamba-2's published initialisation draws them (`A` uniform in [1,
16] over the heads, `dt0` log-uniform in [0.001, 0.1], Mamba-2's defaults:
this config names no `time_step` keys), so a head's decay a position lies
between 0.2 and 0.999 and a state lost between two spans shows. `D` is ones.

Every value is one a bfloat16 holds exactly (`schemes/keye_vl2.py`)."""
import numpy as np

from benchmark.schemes.keye_vl2 import _KEEP, _exact
from benchmark.weights import _HALF_WIDTH

DT_MIN, DT_MAX = 1e-3, 1e-1


def inner_width(config):
    return config["mamba_n_heads"] * config["mamba_d_head"]


def conv_channels(config):
    return inner_width(config) \
        + 2 * config["mamba_n_groups"] * config["mamba_d_state"]


def tensors(config, draw):
    if config.get("num_local_experts"):
        raise ValueError("granitemoehybrid with routed experts: this scheme "
                         "writes shared_mlp alone")
    pool = getattr(draw, "pool", None)
    if pool is not None:
        # the draws are views of this pool: cleared once here, every later
        # draw is exact and still a view
        pool.view(np.uint16)[...] &= _KEEP
    plain = draw

    def draw(shape, mean=0.0):      # noqa: F811 (the exact draw, from here)
        values = plain(shape, mean) if mean else plain(shape)
        return _exact(values) if mean or pool is None else values

    def unit(shape):    # the pool's draw over its half-width: in [-1, 1]
        return np.asarray(draw(shape), np.float32) / _HALF_WIDTH

    def exact(values):
        return _exact(np.asarray(values, np.float32).astype(np.float16))

    d = config["hidden_size"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    head = d // heads
    ssm_heads, inner = config["mamba_n_heads"], inner_width(config)
    channels = conv_channels(config)
    width = config["shared_intermediate_size"]
    out = {"model.embed_tokens.weight": draw((config["vocab_size"], d)),
           "model.norm.weight": draw((d,), 1.0)}
    for i in range(config["num_hidden_layers"]):
        root = f"model.layers.{i}."
        out[root + "input_layernorm.weight"] = draw((d,), 1.0)
        out[root + "post_attention_layernorm.weight"] = draw((d,), 1.0)
        kind = config["layer_types"][i]
        if kind == "mamba":
            mix = root + "mamba."
            out[mix + "in_proj.weight"] = draw((inner + channels + ssm_heads,
                                                d))
            out[mix + "conv1d.weight"] = draw(
                (channels, 1, config["mamba_d_conv"]))
            out[mix + "conv1d.bias"] = draw((channels,))
            out[mix + "A_log"] = exact(np.log(
                8.5 + 7.5 * unit((ssm_heads,))))
            dt0 = np.exp(0.5 * (1.0 + unit((ssm_heads,)))
                         * (np.log(DT_MAX) - np.log(DT_MIN))
                         + np.log(DT_MIN))
            out[mix + "dt_bias"] = exact(dt0 + np.log(-np.expm1(-dt0)))
            out[mix + "D"] = np.ones((ssm_heads,), np.float16)
            out[mix + "norm.weight"] = draw((inner,), 1.0)
            out[mix + "out_proj.weight"] = draw((d, inner))
        elif kind == "attention":
            mix = root + "self_attn."
            out[mix + "q_proj.weight"] = draw((heads * head, d))
            out[mix + "k_proj.weight"] = draw((groups * head, d))
            out[mix + "v_proj.weight"] = draw((groups * head, d))
            out[mix + "o_proj.weight"] = draw((d, heads * head))
        else:
            raise ValueError(f"layer {i}: no mixer {kind!r}")
        out[root + "shared_mlp.input_linear.weight"] = draw((2 * width, d))
        out[root + "shared_mlp.output_linear.weight"] = draw((d, width))
    return out
