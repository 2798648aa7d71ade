"""Kimi-K2 (`model_type` "kimi_k2"): DeepSeek-V3's published state dict.
`nn.Linear` kernels [out, in], no biases, norm scales drawn about 1. A
layer below `first_k_dense_replace` has a dense `mlp`; the others a router
(`mlp.gate.weight` and `mlp.gate.e_score_correction_bias`, both over the
PUBLISHED number of routed experts: a chip that holds a share still routes
over all of them), the held experts under their published indices
(`n_routed_experts` of them from `experts_held_from`) and the shared
expert.

**The router is drawn so that a share's load does not swing with the seed.**
A published router is balanced by training (the correction bias is what
does it); rows drawn at random are not: each expert's mean logit is off by
what its row makes of the hidden state's common component (0.19 of the
logits' spread, which is a popularity of 0.6x to 1.5x at a threshold two
spreads up), and a bias of the pool's own spread (0.02 beside sigmoids that
lie within 0.03 of 1 at that threshold) makes it 0.1x to 3x. Twelve experts
a layer then held 0.20 to 0.31 assignments a token by seed, and tok/s moved
3% where its bound is 1% (root PERF.md, PR 31). So the rows come in
antithetic pairs (row 2j+1 = -row 2j: the common component moves the two
opposite ways and the pair's load stays, to first order), and the bias is
the pool's draw divided by 16 (a power of two: exact): not zeros, which
would leave the mechanism untried, and not the term that decides.

Every value is one a bfloat16 holds exactly, as a published bfloat16
checkpoint's are (`schemes/keye_vl2.py` says why): the program rounds
nothing when it loads the file and the reference reads the same model."""
import numpy as np

from benchmark.schemes.keye_vl2 import _KEEP, _exact


def router_width(config):
    """The router's outputs: the published count of routed experts."""
    return config.get("published", {}).get("n_routed_experts",
                                           config["n_routed_experts"])


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
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, q_rank = config["kv_lora_rank"], config["q_lora_rank"]
    width = config["moe_intermediate_size"]
    first = config.get("experts_held_from", 0)
    routed = router_width(config)

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
        out[root + "input_layernorm.weight"] = draw((d,), 1.0)
        out[att + "q_a_proj.weight"] = draw((q_rank, d))
        out[att + "q_a_layernorm.weight"] = draw((q_rank,), 1.0)
        out[att + "q_b_proj.weight"] = draw((heads * (nope + rope), q_rank))
        out[att + "kv_a_proj_with_mqa.weight"] = draw((rank + rope, d))
        out[att + "kv_a_layernorm.weight"] = draw((rank,), 1.0)
        out[att + "kv_b_proj.weight"] = draw(
            (heads * (nope + config["v_head_dim"]), rank))
        out[att + "o_proj.weight"] = draw((d, heads * config["v_head_dim"]))
        out[root + "post_attention_layernorm.weight"] = draw((d,), 1.0)
        if i < config["first_k_dense_replace"]:
            mlp(out, root + "mlp.", config["intermediate_size"])
            continue
        half = draw((routed // 2, d))
        out[root + "mlp.gate.weight"] = np.stack([half, -half], 1).reshape(
            routed, d)
        out[root + "mlp.gate.e_score_correction_bias"] = draw(
            (routed,)) * np.float16(0.0625)
        for e in range(first, first + config["n_routed_experts"]):
            mlp(out, f"{root}mlp.experts.{e}.", width)
        mlp(out, root + "mlp.shared_experts.",
            width * config["n_shared_experts"])
    return out
