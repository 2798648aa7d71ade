"""LFM2-MoE (`model_type` "lfm2_moe"): the published state dict.
`nn.Linear` kernels [out, in], no biases, norm scales drawn about 1. Layer
i's mixer is `layer_types[i]`: a gated short convolution (`conv.{in_proj,
conv,out_proj}.weight`, the depthwise kernel `[hidden, 1, conv_L_cache]`) or
grouped-query attention (`self_attn.{q_proj,k_proj,v_proj,out_proj}.weight`,
`q_layernorm`, `k_layernorm` over a head's width). A layer below
`num_dense_layers` has a dense `feed_forward.{w1,w2,w3}`; the others a router
(`feed_forward.gate.weight`, `feed_forward.expert_bias`) and
`feed_forward.experts.E.{w1,w2,w3}` for every expert: none is left out and
none is shared. `model.embedding_norm` is the final norm; the head is
`model.embed_tokens`, tied, so no `lm_head` is written.

**The router** is drawn in antithetic pairs (row 2j+1 = -row 2j) and the
bias is the pool's draw divided by 16, as `schemes/kimi_k2.py` says why: an
expert's load then does not swing with the seed, and the bias is neither
zeros (the mechanism untried) nor the term that decides.

Every value is one a bfloat16 holds exactly (`schemes/keye_vl2.py`)."""
import numpy as np

from benchmark.schemes.keye_vl2 import _KEEP, _exact


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

    d, heads = config["hidden_size"], config["num_attention_heads"]
    head = d // heads
    groups = config["num_key_value_heads"]
    experts, width = config["num_experts"], config["moe_intermediate_size"]

    def mlp(out, root, f):
        out[root + "w1.weight"] = draw((f, d))
        out[root + "w2.weight"] = draw((d, f))
        out[root + "w3.weight"] = draw((f, d))

    out = {
        "model.embed_tokens.weight": draw((config["vocab_size"], d)),
        "model.embedding_norm.weight": draw((d,), 1.0),
    }
    for i in range(config["num_hidden_layers"]):
        root = f"model.layers.{i}."
        out[root + "operator_norm.weight"] = draw((d,), 1.0)
        if config["layer_types"][i] == "conv":
            out[root + "conv.in_proj.weight"] = draw((3 * d, d))
            out[root + "conv.conv.weight"] = draw(
                (d, 1, config["conv_L_cache"]))
            out[root + "conv.out_proj.weight"] = draw((d, d))
        else:
            att = root + "self_attn."
            out[att + "q_proj.weight"] = draw((heads * head, d))
            out[att + "k_proj.weight"] = draw((groups * head, d))
            out[att + "v_proj.weight"] = draw((groups * head, d))
            out[att + "out_proj.weight"] = draw((d, heads * head))
            out[att + "q_layernorm.weight"] = draw((head,), 1.0)
            out[att + "k_layernorm.weight"] = draw((head,), 1.0)
        out[root + "ffn_norm.weight"] = draw((d,), 1.0)
        if i < config["num_dense_layers"]:
            mlp(out, root + "feed_forward.", config["intermediate_size"])
            continue
        half = draw((experts // 2, d))
        out[root + "feed_forward.gate.weight"] = np.stack(
            [half, -half], 1).reshape(experts, d)
        out[root + "feed_forward.expert_bias"] = draw(
            (experts,)) * np.float16(0.0625)
        for e in range(experts):
            mlp(out, f"{root}feed_forward.experts.{e}.", width)
    return out
