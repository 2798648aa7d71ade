"""Qwen3-Next (`model_type` "qwen3_next"): the published state dict.
`nn.Linear` kernels [out, in], no biases. A layer is full attention where
`(i + 1) % full_attention_interval == 0` (`self_attn.{q,k,v,o}_proj`,
`q_norm`, `k_norm`; a head of `q_proj` is `[query | gate]`) and Gated
DeltaNet elsewhere (`linear_attn.{in_proj_qkvz, in_proj_ba, conv1d,
out_proj}.weight`, `dt_bias`, `A_log`, `norm.weight`; the rows of the two
input projections grouped by key head). Every layer has a router
(`mlp.gate.weight` over the PUBLISHED number of experts: a chip that holds a
share still routes over all of them), the held experts under their published
indices (`num_experts` of them from `experts_held_from`), the shared expert
and its gate.

**Norm weights.** The family's RMSNorm is zero-centred (`1 + w`), and the
checkpoint stores `w` near 0: small draws, the reader adds the 1. The
Gated DeltaNet's output norm is the plain kind, drawn about 1.

**The decay.** `exp(g) = exp(-exp(A_log) softplus(a + dt_bias))` a
position. The published initialisation spreads the heads' decays; a pool
draw of standard deviation 0.02 for `A_log` would give every head `exp(-1 x
0.69)` = 0.5, a state that forgets in ten positions, and a comparison that
could not see a state lost between two spans. So `A_log` is drawn uniform
in [-6.5, 0] (at `a + dt_bias` = 0 a decay of 2**-exp(A_log): 0.999 to 0.5 a
position over the heads) and `dt_bias` is a small draw.

**The router** is drawn in antithetic pairs (row 2j+1 = -row 2j), as
`schemes/kimi_k2.py` says why: a share's load then does not swing with the
seed. The held half (experts 0-255) is 128 whole pairs.

Every value is one a bfloat16 holds exactly (`schemes/keye_vl2.py`)."""
import numpy as np

from benchmark.schemes.keye_vl2 import _KEEP, _exact
from benchmark.weights import _HALF_WIDTH


def router_width(config):
    """The router's outputs: the published count of experts."""
    return config.get("published", {}).get("num_experts",
                                           config["num_experts"])


def is_full_attention(config, layer):
    return (layer + 1) % config["full_attention_interval"] == 0


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

    d, head = config["hidden_size"], config["head_dim"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    width = config["moe_intermediate_size"]
    first = config.get("experts_held_from", 0)
    routed = router_width(config)

    def mlp(out, root, f):
        out[root + "gate_proj.weight"] = draw((f, d))
        out[root + "up_proj.weight"] = draw((f, d))
        out[root + "down_proj.weight"] = draw((d, f))

    out = {
        "model.embed_tokens.weight": draw((config["vocab_size"], d)),
        "model.norm.weight": draw((d,)),
        "lm_head.weight": draw((config["vocab_size"], d)),
    }
    for i in range(config["num_hidden_layers"]):
        root = f"model.layers.{i}."
        out[root + "input_layernorm.weight"] = draw((d,))
        if is_full_attention(config, i):
            att = root + "self_attn."
            out[att + "q_proj.weight"] = draw((2 * heads * head, d))
            out[att + "k_proj.weight"] = draw((groups * head, d))
            out[att + "v_proj.weight"] = draw((groups * head, d))
            out[att + "o_proj.weight"] = draw((d, heads * head))
            out[att + "q_norm.weight"] = draw((head,))
            out[att + "k_norm.weight"] = draw((head,))
        else:
            att = root + "linear_attn."
            out[att + "in_proj_qkvz.weight"] = draw(
                (2 * hk * dk + 2 * hv * dv, d))
            out[att + "in_proj_ba.weight"] = draw((2 * hv, d))
            out[att + "conv1d.weight"] = draw(
                (2 * hk * dk + hv * dv, 1, config["linear_conv_kernel_dim"]))
            out[att + "dt_bias"] = draw((hv,))
            spread = np.asarray(draw((hv,)), np.float32) / _HALF_WIDTH
            out[att + "A_log"] = _exact(
                (-3.25 * (1.0 - spread)).astype(np.float16))
            out[att + "norm.weight"] = draw((dv,), 1.0)
            out[att + "out_proj.weight"] = draw((d, hv * dv))
        out[root + "post_attention_layernorm.weight"] = draw((d,))
        half = draw((routed // 2, d))
        out[root + "mlp.gate.weight"] = np.stack([half, -half], 1).reshape(
            routed, d)
        for e in range(first, first + config["num_experts"]):
            mlp(out, f"{root}mlp.experts.{e}.", width)
        mlp(out, root + "mlp.shared_expert.",
            config["shared_expert_intermediate_size"])
        out[root + "mlp.shared_expert_gate.weight"] = draw((1, d))
    return out
