"""Mellum (`model_type` "mellum"): Qwen3-MoE's state dict. `nn.Linear`
kernels [out, in], no biases, norm scales drawn about 1. Every layer's
attention has `num_attention_heads` query heads and `num_key_value_heads` KV
heads of `head_dim` (`self_attn.{q_proj,k_proj,v_proj,o_proj}`), `q_norm` and
`k_norm` over a head's width, and no gate; every layer's FFN is a router
(`mlp.gate.weight`) and `mlp.experts.E.{gate_proj,up_proj,down_proj}` for
every expert (all of `mlp_layer_types` are "sparse": there is no dense layer
and no shared expert). Embedding and head are two tables.

**The router** is drawn in antithetic pairs (row 2j+1 = -row 2j), as
`schemes/kimi_k2.py` says why: an expert's load then does not swing with
the seed.

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

    d, head = config["hidden_size"], config["head_dim"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    experts, width = config["num_experts"], config["moe_intermediate_size"]
    out = {
        "model.embed_tokens.weight": draw((config["vocab_size"], d)),
        "model.norm.weight": draw((d,), 1.0),
        "lm_head.weight": draw((config["vocab_size"], d)),
    }
    for i in range(config["num_hidden_layers"]):
        if config["mlp_layer_types"][i] != "sparse":
            raise ValueError(f"layer {i} is {config['mlp_layer_types'][i]}: "
                             "the scheme knows expert layers only")
        root = f"model.layers.{i}."
        att = root + "self_attn."
        out[root + "input_layernorm.weight"] = draw((d,), 1.0)
        out[att + "q_proj.weight"] = draw((heads * head, d))
        out[att + "k_proj.weight"] = draw((groups * head, d))
        out[att + "v_proj.weight"] = draw((groups * head, d))
        out[att + "o_proj.weight"] = draw((d, heads * head))
        out[att + "q_norm.weight"] = draw((head,), 1.0)
        out[att + "k_norm.weight"] = draw((head,), 1.0)
        out[root + "post_attention_layernorm.weight"] = draw((d,), 1.0)
        half = draw((experts // 2, d))
        out[root + "mlp.gate.weight"] = np.stack([half, -half], 1).reshape(
            experts, d)
        for e in range(experts):
            stem = f"{root}mlp.experts.{e}."
            out[stem + "gate_proj.weight"] = draw((width, d))
            out[stem + "up_proj.weight"] = draw((width, d))
            out[stem + "down_proj.weight"] = draw((d, width))
    return out
