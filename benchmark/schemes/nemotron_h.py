"""Nemotron-H (`model_type` "nemotron_h", Nemotron-3-Super): the published
state dict as the configuration's `assumed.checkpoint_keys` has it.
`nn.Linear` kernels [out, in], no biases but the convolution's. Layer `i` is
what letter `i` of `hybrid_override_pattern` says, ONE sublayer after one
`norm`: `M` a Mamba-2 mixer (`mixer.{in_proj,out_proj}.weight`,
`mixer.conv1d.{weight,bias}`, `mixer.{A_log,D,dt_bias}`, `mixer.norm.weight`
over the inner width), `*` an attention (`mixer.{q,k,v,o}_proj.weight`), `E`
an expert layer: a router (`mixer.gate.weight` and
`mixer.gate.e_score_correction_bias`, both over the PUBLISHED number of routed
experts: a chip that holds a share still routes over all of them), the two
latent projections (`mixer.fc1_latent_proj`, down to the latent, and
`mixer.fc2_latent_proj`, back up), the held experts under their published
indices (`n_routed_experts` of them from `experts_held_from`), two matrices
each in the latent's width, and the shared expert on the full width. The
`mtp.*` tensors are no part of generation and are not written.

**The decay.** `a = exp(-exp(A_log) softplus(dt + dt_bias))` a position. A
pool draw of standard deviation 0.02 for `A_log` and `dt_bias` would give
every head `exp(-1 x 0.69)` = 0.5, a state that forgets in ten positions,
and a comparison that could not see a state lost between two spans. So they
are drawn as Mamba-2's published initialisation draws them, whose three
constants are the configuration's keys: `A` uniform in [1, 16] over the
heads, `A_log = log A`; `dt0` log-uniform in [`time_step_min`,
`time_step_max`], floored at `time_step_floor`, `dt_bias =
softplus^-1(dt0)`: at a projected `dt` of 0 a head's decay a position lies
between exp(-16 x 0.1) = 0.2 and exp(-0.001) = 0.999. `D` is ones, as
published. (Both spreads come from the pool's draw over its half-width,
which is uniform in [-1, 1].)

**The router** is drawn in antithetic pairs (row 2j+1 = -row 2j) and its bias
is the pool's draw over 16, as `schemes/kimi_k2.py` says why: a share's load
then does not swing with the seed. The held quarter (experts 0-127) is 64
whole pairs.

Every value is one a bfloat16 holds exactly (`schemes/keye_vl2.py`)."""
import numpy as np

from benchmark.schemes.keye_vl2 import _KEEP, _exact
from benchmark.weights import _HALF_WIDTH


def router_width(config):
    """The router's outputs: the published count of routed experts."""
    return config.get("published", {}).get("n_routed_experts",
                                           config["n_routed_experts"])


def inner_width(config):
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def conv_channels(config):
    return inner_width(config) \
        + 2 * config["n_groups"] * config["ssm_state_size"]


def tensors(config, draw):
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

    d, head = config["hidden_size"], config["head_dim"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    ssm_heads, inner = config["mamba_num_heads"], inner_width(config)
    channels = conv_channels(config)
    latent, width = config["moe_latent_size"], \
        config["moe_intermediate_size"]
    first = config.get("experts_held_from", 0)
    routed = router_width(config)
    dt_min, dt_max = config["time_step_min"], config["time_step_max"]

    def mlp(out, root, f, d_in):
        out[root + "up_proj.weight"] = draw((f, d_in))
        out[root + "down_proj.weight"] = draw((d_in, f))

    out = {
        "backbone.embeddings.weight": draw((config["vocab_size"], d)),
        "backbone.norm_f.weight": draw((d,), 1.0),
        "lm_head.weight": draw((config["vocab_size"], d)),
    }
    pattern = config["hybrid_override_pattern"]
    for i in range(config["num_hidden_layers"]):
        root = f"backbone.layers.{i}."
        mix = root + "mixer."
        out[root + "norm.weight"] = draw((d,), 1.0)
        if pattern[i] == "M":
            out[mix + "in_proj.weight"] = draw((inner + channels + ssm_heads,
                                                d))
            out[mix + "conv1d.weight"] = draw(
                (channels, 1, config["conv_kernel"]))
            out[mix + "conv1d.bias"] = draw((channels,))
            out[mix + "A_log"] = exact(np.log(
                8.5 + 7.5 * unit((ssm_heads,))))
            dt0 = np.maximum(np.exp(
                0.5 * (1.0 + unit((ssm_heads,)))
                * (np.log(dt_max) - np.log(dt_min)) + np.log(dt_min)),
                config["time_step_floor"])
            out[mix + "dt_bias"] = exact(dt0 + np.log(-np.expm1(-dt0)))
            out[mix + "D"] = np.ones((ssm_heads,), np.float16)
            out[mix + "norm.weight"] = draw((inner,), 1.0)
            out[mix + "out_proj.weight"] = draw((d, inner))
        elif pattern[i] == "*":
            out[mix + "q_proj.weight"] = draw((heads * head, d))
            out[mix + "k_proj.weight"] = draw((groups * head, d))
            out[mix + "v_proj.weight"] = draw((groups * head, d))
            out[mix + "o_proj.weight"] = draw((d, heads * head))
        elif pattern[i] == "E":
            half = draw((routed // 2, d))
            out[mix + "gate.weight"] = np.stack([half, -half], 1).reshape(
                routed, d)
            out[mix + "gate.e_score_correction_bias"] = draw(
                (routed,)) * np.float16(0.0625)
            out[mix + "fc1_latent_proj.weight"] = draw((latent, d))
            out[mix + "fc2_latent_proj.weight"] = draw((d, latent))
            for e in range(first, first + config["n_routed_experts"]):
                mlp(out, f"{mix}experts.{e}.", width, latent)
            mlp(out, mix + "shared_experts.",
                config["moe_shared_expert_intermediate_size"], d)
        else:
            raise ValueError(f"layer {i}: no sublayer {pattern[i]!r}")
    return out
