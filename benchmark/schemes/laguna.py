"""Laguna (`model_type` "laguna"): Qwen3-MoE's state dict with a gate on the
attention's output. `nn.Linear` kernels [out, in], no biases, norm scales
drawn about 1. Layer i's attention has `num_attention_heads_per_layer[i]`
query heads (`self_attn.{q_proj,o_proj}` differ in shape by the layer's
kind, `g_proj` is one row a head), `num_key_value_heads` KV heads of
`head_dim`, `q_norm` and `k_norm` over a head's width. A layer whose
`mlp_layer_types` entry is "dense" has `mlp.{gate_proj,up_proj,down_proj}`
of `intermediate_size`; the others a router (`mlp.gate.weight`),
`mlp.experts.E.{gate_proj,up_proj,down_proj}` for every expert (none is
left out), `mlp.shared_expert.*` and `mlp.shared_expert_gate.weight`.
Embedding and head are two tables.

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
    groups = config["num_key_value_heads"]
    experts, width = config["num_experts"], config["moe_intermediate_size"]

    def mlp(out, root, f):
        out[root + "gate_proj.weight"] = draw((f, d))
        out[root + "up_proj.weight"] = draw((f, d))
        out[root + "down_proj.weight"] = draw((d, f))

    out = {
        "model.embed_tokens.weight": draw((config["vocab_size"], d)),
        "model.norm.weight": draw((d,), 1.0),
        "lm_head.weight": draw((config["vocab_size"], d)),
    }
    for i in range(config["num_hidden_layers"]):
        root = f"model.layers.{i}."
        att = root + "self_attn."
        heads = config["num_attention_heads_per_layer"][i]
        out[root + "input_layernorm.weight"] = draw((d,), 1.0)
        out[att + "q_proj.weight"] = draw((heads * head, d))
        out[att + "k_proj.weight"] = draw((groups * head, d))
        out[att + "v_proj.weight"] = draw((groups * head, d))
        out[att + "o_proj.weight"] = draw((d, heads * head))
        out[att + "g_proj.weight"] = draw((heads, d))
        out[att + "q_norm.weight"] = draw((head,), 1.0)
        out[att + "k_norm.weight"] = draw((head,), 1.0)
        out[root + "post_attention_layernorm.weight"] = draw((d,), 1.0)
        if config["mlp_layer_types"][i] == "dense":
            mlp(out, root + "mlp.", config["intermediate_size"])
            continue
        half = draw((experts // 2, d))
        out[root + "mlp.gate.weight"] = np.stack([half, -half], 1).reshape(
            experts, d)
        for e in range(experts):
            mlp(out, f"{root}mlp.experts.{e}.", width)
        mlp(out, root + "mlp.shared_expert.",
            config["shared_expert_intermediate_size"])
        out[root + "mlp.shared_expert_gate.weight"] = draw((1, d))
    return out
