"""Keye-VL-2.0's language model (`model_type` "keye_vl2"): the Qwen3-MoE
state dict this config repeats key for key, and the indexer under
`self_attn.indexer` in our naming (the checkpoint's is not in the catalog:
`assumed` in the configuration's file).

Every expert matrix (1.57 M values) is a view of the pool; only the two
vocabulary tables (311 M values each) read it round again.

Every value is one a bfloat16 holds exactly, as a published bfloat16
checkpoint's are: the file is float16 (`benchmark/weights.py`), so the three
lowest bits of each value's mantissa are cleared. The program's loader
rounds the file to bfloat16 and the reference reads it as float32; with
exact values both hold the same model. Rounded, they held two, 2**-9 apart
in every weight, and this architecture's discrete choices (a query's kept
keys, a token's experts) turn that into different outputs (root PERF.md,
PR 27)."""
import numpy as np

_KEEP = np.uint16(0xFFF8)       # float16: sign, exponent, 7 of 10 mantissa bits


def _exact(values):
    """The float16 `values` with each rounded toward zero to a bfloat16."""
    return (values.view(np.uint16) & _KEEP).view(np.float16)


def tensors(config, draw):
    """`nn.Linear` kernels [out, in], no biases; norm scales drawn about 1."""
    pool = getattr(draw, "pool", None)
    if pool is not None:
        # the draws are views of this pool: cleared once here, every later
        # draw is exact and still a view (8.75 GB of tensors, 134 MB held)
        pool.view(np.uint16)[...] &= _KEEP
    plain = draw

    def draw(shape, mean=0.0):      # noqa: F811 (the exact draw, from here)
        values = plain(shape, mean) if mean else plain(shape)
        return _exact(values) if mean or pool is None else values

    d = config["hidden_size"]
    head = config["head_dim"]
    q_out = config["num_attention_heads"] * head
    kv_out = config["num_key_value_heads"] * head
    width = config["moe_intermediate_size"]
    sa = config["sa_config"]
    index_out = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    out = {
        "model.embed_tokens.weight": draw((config["vocab_size"], d)),
        "model.norm.weight": draw((d,), 1.0),
        "lm_head.weight": draw((config["vocab_size"], d)),
    }
    for i in range(config["num_hidden_layers"]):
        root = f"model.layers.{i}."
        att = root + "self_attn."
        out[root + "input_layernorm.weight"] = draw((d,), 1.0)
        out[att + "q_proj.weight"] = draw((q_out, d))
        out[att + "k_proj.weight"] = draw((kv_out, d))
        out[att + "v_proj.weight"] = draw((kv_out, d))
        out[att + "o_proj.weight"] = draw((d, q_out))
        out[att + "q_norm.weight"] = draw((head,), 1.0)
        out[att + "k_norm.weight"] = draw((head,), 1.0)
        out[att + "indexer.wq.weight"] = draw((index_out, d))
        out[att + "indexer.wk.weight"] = draw((sa["indexer_head_dim"], d))
        out[att + "indexer.weights_proj.weight"] = draw(
            (sa["indexer_num_heads"], d))
        out[att + "indexer.k_norm.weight"] = draw(
            (sa["indexer_head_dim"],), 1.0)
        out[att + "indexer.k_norm.bias"] = draw((sa["indexer_head_dim"],))
        out[root + "post_attention_layernorm.weight"] = draw((d,), 1.0)
        out[root + "mlp.gate.weight"] = draw((config["num_experts"], d))
        for e in range(config["num_experts"]):
            expert = f"{root}mlp.experts.{e}."
            out[expert + "gate_proj.weight"] = draw((width, d))
            out[expert + "up_proj.weight"] = draw((width, d))
            out[expert + "down_proj.weight"] = draw((d, width))
    return out
