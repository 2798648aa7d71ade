"""MiniCPM-SALA (`model_type` "minicpm_sala"): Llama-style keys with q/k
norms and an output gate in every layer's `self_attn`, whatever its mixer
(`configs/minicpm-sala.json`, `assumed.checkpoint_keys`). `nn.Linear`
kernels [out, in], no biases, norm scales drawn about 1. A layer whose
`mixer_types` entry is "minicpm4" has `num_key_value_heads` KV heads of
`head_dim`; a "lightning-attn" layer has `lightning_nkv` of
`lightning_head_dim` for k and v, `lightning_nh` for q, and an `o_norm`
over its joined heads. Every layer has the dense `mlp`. Embedding and head
are two tables. The decays of a lightning layer are no tensors: they follow
from the layer's index (`assumed.lightning_decay`).

Every value is one a bfloat16 holds exactly: the pool's low mantissa bits
are cleared once, so a draw stays a view of it, and a scale about 1 is
rounded after the 1 is added."""
import numpy as np

_KEEP = np.uint16(0xFFF8)       # a float16's 10 mantissa bits -> bfloat16's 7


def tensors(config, draw):
    pool = getattr(draw, "pool", None)
    if pool is not None:
        pool.view(np.uint16)[...] &= _KEEP
    else:       # a draw with no pool to clear: each tensor on its own
        plain = draw

        def draw(shape, mean=0.0):      # noqa: F811
            values = np.asarray(plain(shape, mean) if mean else plain(shape),
                                np.float16)
            return (values.view(np.uint16) & _KEEP).view(np.float16)

    def scale(n):       # about 1: exact in bfloat16 below 2 with 7 bits
        values = np.asarray(draw((n,), 1.0), np.float32)
        return (np.round(values * 128.0) / 128.0).astype(np.float16)

    d, f = config["hidden_size"], config["intermediate_size"]
    out = {
        "model.embed_tokens.weight": draw((config["vocab_size"], d)),
        "model.norm.weight": scale(d),
        "lm_head.weight": draw((config["vocab_size"], d)),
    }
    for i in range(config["num_hidden_layers"]):
        root = f"model.layers.{i}."
        att = root + "self_attn."
        if config["mixer_types"][i] == "minicpm4":
            heads, groups = config["num_attention_heads"], \
                config["num_key_value_heads"]
            head = config["head_dim"]
        else:
            heads, groups = config["lightning_nh"], config["lightning_nkv"]
            head = config["lightning_head_dim"]
        out[root + "input_layernorm.weight"] = scale(d)
        out[att + "q_proj.weight"] = draw((heads * head, d))
        out[att + "k_proj.weight"] = draw((groups * head, d))
        out[att + "v_proj.weight"] = draw((groups * head, d))
        out[att + "o_proj.weight"] = draw((d, heads * head))
        out[att + "o_gate.weight"] = draw((heads * head, d))
        out[att + "q_norm.weight"] = scale(head)
        out[att + "k_norm.weight"] = scale(head)
        if config["mixer_types"][i] != "minicpm4":
            out[att + "o_norm.weight"] = scale(heads * head)
        out[root + "post_attention_layernorm.weight"] = scale(d)
        out[root + "mlp.gate_proj.weight"] = draw((f, d))
        out[root + "mlp.up_proj.weight"] = draw((f, d))
        out[root + "mlp.down_proj.weight"] = draw((d, f))
    return out
