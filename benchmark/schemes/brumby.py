"""Brumby (`model_type` "brumby"; Brumby-14B-Base): Qwen3's Llama-style keys
with q/k norms in every layer's `self_attn` and, beside `{q,k,v,o}_proj`,
the gate's projection `g_proj` [num_key_value_heads, hidden]
(`configs/brumby-14b-base.json`, `assumed.checkpoint_keys` and
`assumed.gate`). `nn.Linear` kernels [out, in], no biases, norm scales drawn
about 1. Every layer is alike and has the dense `mlp`. Embedding and head
are two tables.

**The gate** is drawn like every other projection (standard deviation
0.02): over a normed input of 5,120 lanes its output has a standard
deviation of 1.4, so `sigmoid` of it, a position's decay, lies between 0.05
and 0.95 with its median at a half, a KV head's memory runs from a position
to tens of them, and a state lost between two spans or two steps shows in
the logits.

Every value is one a bfloat16 holds exactly: the pool's low mantissa bits
are cleared once, so a draw stays a view of it, and a scale about 1 is
rounded after the 1 is added."""
import numpy as np

from benchmark.schemes.keye_vl2 import _KEEP


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
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    head = config["head_dim"]
    out = {
        "model.embed_tokens.weight": draw((config["vocab_size"], d)),
        "model.norm.weight": scale(d),
        "lm_head.weight": draw((config["vocab_size"], d)),
    }
    for i in range(config["num_hidden_layers"]):
        root = f"model.layers.{i}."
        att = root + "self_attn."
        out[root + "input_layernorm.weight"] = scale(d)
        out[att + "q_proj.weight"] = draw((heads * head, d))
        out[att + "k_proj.weight"] = draw((groups * head, d))
        out[att + "v_proj.weight"] = draw((groups * head, d))
        out[att + "o_proj.weight"] = draw((d, heads * head))
        out[att + "g_proj.weight"] = draw((groups, d))
        out[att + "q_norm.weight"] = scale(head)
        out[att + "k_norm.weight"] = scale(head)
        out[root + "post_attention_layernorm.weight"] = scale(d)
        out[root + "mlp.gate_proj.weight"] = draw((f, d))
        out[root + "mlp.up_proj.weight"] = draw((f, d))
        out[root + "mlp.down_proj.weight"] = draw((d, f))
    return out
