"""GPT-2 model family: causal-decoder shards with the 4-way sublayer split.

NEW capability beyond the reference, which ships only encoder families
(ViT/DeiT/BERT — /root/reference/model_cfg.py:24-43). A causal decoder slots
into the same shard/pipeline machinery because a GPT-2 block is pre-LN like
ViT's (reference vit.py:55-70), so the 4-sublayer cut points carry over:
  sub 0: ln_1 -> causal self-attention       payload becomes (ctx, residual)
  sub 1: attn output proj + residual         payload becomes hidden
  sub 2: ln_2 -> MLP-up + GeLU(tanh)         payload becomes (mlp_h, residual)
  sub 3: MLP-down + residual                 payload becomes hidden
First shard: token + learned position embeddings. Last shard: final
LayerNorm + tied LM head -> per-token vocab logits.

Parameters reuse the ViT sublayer names (ln_before/q/k/v/attn_out/ln_after/
mlp_up/mlp_down), so the Megatron TP spec table and the SPMD driver's
stacked-block sharding apply unchanged; only the block body differs (causal
mask, tanh-approximate GeLU — HF `gelu_new`).

Weight format: HF `GPT2LMHeadModel`/`GPT2Model` state-dict npz. HF stores
these as `Conv1D` with kernels already [in, out] (unlike `nn.Linear`), so no
transpose; the fused `c_attn` [D, 3D] kernel splits into q/k/v at load time
(the same trick DeiT uses for its fused qkv, deit.py:131-156).
"""
from __future__ import annotations

from typing import Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig
from .layers import TransformerConfig, dense, gelu_new, layer_norm, self_attention
from .shard import FamilySpec, build_shard_params

SUBLAYER_PARAMS = {
    0: ("ln_before", "q", "k", "v"),
    1: ("attn_out",),
    2: ("ln_after", "mlp_up"),
    3: ("mlp_down",),
}

# routed-FFN (cfg.n_experts > 0) variant: the whole switch-FFN lives in
# sublayer 2 (capacity routing cannot span a pipeline cut); sublayer 3 is
# the parameter-free residual add
MOE_SUBLAYER_PARAMS = {
    0: ("ln_before", "q", "k", "v"),
    1: ("attn_out",),
    2: ("ln_after", "moe"),
    3: (),
}


def embed(p: Dict, input_ids: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Token embedding + learned position embedding (HF `GPT2Model.forward`)."""
    seq_len = input_ids.shape[1]
    return jnp.take(p["wte"], input_ids, axis=0) + p["wpe"][:seq_len][None]


def sublayer(p: Dict, sub: int, data, cfg: TransformerConfig,
             attention_fn=None):
    """One of the 4 schedulable sublayers (pre-LN block, causal attention).

    `attention_fn(qkv_params, x, num_heads, causal=...)` overrides the
    attention core (sequence-parallel execution swaps in causal ring
    attention, parallel/spmd.py)."""
    if sub == 0:
        normed = layer_norm(p["ln_before"], data, cfg.layer_norm_eps)
        ctx = (attention_fn or self_attention)(
            {"q": p["q"], "k": p["k"], "v": p["v"]}, normed,
            cfg.num_attention_heads, causal=True)
        return (ctx, data)
    if sub == 1:
        ctx, skip = data
        return dense(p["attn_out"], ctx) + skip
    if sub == 2:
        normed = layer_norm(p["ln_after"], data, cfg.layer_norm_eps)
        if cfg.n_experts:
            # switch-FFN (Switch Transformer top-1): the whole routed
            # expert computation lives in sublayer 2 (capacity routing
            # cannot span a pipeline cut), so the sublayer-2 edge carries
            # (delta, residual) like the dense path's (mlp_h, residual)
            from ..parallel.expert import moe_ffn_delta
            delta = moe_ffn_delta(p["moe"], normed, cfg.n_experts,
                                  cfg.capacity_factor, act=gelu_new)
            return (delta, data)
        return (gelu_new(dense(p["mlp_up"], normed)), data)
    if sub == 3:
        mlp_h, skip = data
        if cfg.n_experts:
            return mlp_h + skip      # delta from sublayer 2 + residual
        return dense(p["mlp_down"], mlp_h) + skip
    raise ValueError(f"sublayer must be 0..3, got {sub}")


def finalize(p: Dict, hidden: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Final LayerNorm + LM head -> [B, S, vocab] logits (tied to wte)."""
    hidden = layer_norm(p["ln"], hidden, cfg.layer_norm_eps)
    return dense(p["head"], hidden)


FAMILY = FamilySpec(name="gpt2", embed=embed, sublayer=sublayer,
                    finalize=finalize, decoder_model=True)


def _a(x, dtype):
    return jnp.asarray(np.asarray(x), dtype=dtype)


def load_params(cfg: TransformerConfig, shard_config: ShardConfig,
                weights: Mapping, dtype=jnp.float32) -> Dict:
    """Build shard params from an HF GPT-2 state-dict npz.

    Accepts `GPT2LMHeadModel` keys (`transformer.`-prefixed + `lm_head.*`)
    and bare `GPT2Model` keys; the LM head falls back to the tied `wte`."""
    keys = set(weights.keys())
    if any(k.startswith("transformer.") for k in keys):
        sd = {k.removeprefix("transformer."): weights[k] for k in keys
              if k.startswith("transformer.")}
        if "lm_head.weight" in keys:
            sd["lm_head.weight"] = weights["lm_head.weight"]
    else:
        sd = weights if isinstance(weights, dict) else dict(weights.items())
    d = cfg.hidden_size

    def get_embed() -> Dict:
        return {"wte": _a(sd["wte.weight"], dtype),
                "wpe": _a(sd["wpe.weight"], dtype)}

    def get_block(block_id: int, subs: tuple) -> Dict:
        root = f"h.{block_id}."
        p: Dict = {}
        if 0 in subs:
            p["ln_before"] = {"scale": _a(sd[root + "ln_1.weight"], dtype),
                              "bias": _a(sd[root + "ln_1.bias"], dtype)}
            w = np.asarray(sd[root + "attn.c_attn.weight"])   # [D, 3D]
            b = np.asarray(sd[root + "attn.c_attn.bias"])     # [3D]
            for i, name in enumerate(("q", "k", "v")):
                p[name] = {"w": _a(w[:, i * d:(i + 1) * d], dtype),
                           "b": _a(b[i * d:(i + 1) * d], dtype)}
        if 1 in subs:
            p["attn_out"] = {"w": _a(sd[root + "attn.c_proj.weight"], dtype),
                             "b": _a(sd[root + "attn.c_proj.bias"], dtype)}
        if 2 in subs:
            p["ln_after"] = {"scale": _a(sd[root + "ln_2.weight"], dtype),
                             "bias": _a(sd[root + "ln_2.bias"], dtype)}
            if cfg.n_experts:
                p["moe"] = {
                    "router": {
                        "w": _a(sd[root + "moe.router.weight"], dtype),
                        "b": _a(sd[root + "moe.router.bias"], dtype)},
                    "experts": {
                        "mlp_up": {
                            "w": _a(sd[root + "moe.experts.c_fc.weight"], dtype),
                            "b": _a(sd[root + "moe.experts.c_fc.bias"], dtype)},
                        "mlp_down": {
                            "w": _a(sd[root + "moe.experts.c_proj.weight"], dtype),
                            "b": _a(sd[root + "moe.experts.c_proj.bias"], dtype)},
                    },
                }
            else:
                p["mlp_up"] = {"w": _a(sd[root + "mlp.c_fc.weight"], dtype),
                               "b": _a(sd[root + "mlp.c_fc.bias"], dtype)}
        if 3 in subs and not cfg.n_experts:
            p["mlp_down"] = {"w": _a(sd[root + "mlp.c_proj.weight"], dtype),
                             "b": _a(sd[root + "mlp.c_proj.bias"], dtype)}
        return p

    def get_final() -> Dict:
        head = sd.get("lm_head.weight", sd["wte.weight"])     # [V, D] tied
        return {"ln": {"scale": _a(sd["ln_f.weight"], dtype),
                       "bias": _a(sd["ln_f.bias"], dtype)},
                "head": {"w": _a(head, dtype).T,
                         "b": jnp.zeros((np.asarray(head).shape[0],), dtype)}}

    return build_shard_params(shard_config, get_embed, get_block, get_final)


def moe_state_dict(cfg: TransformerConfig, seed: int = 0) -> Dict:
    """Deterministic random full-model state dict for MoE configs, in the
    flat npz key layout `load_params` reads (`h.{i}.moe.*` for the routed
    FFN). No pretrained checkpoints exist for this synthetic family, so
    this is the weights-file story (save_model_weights.py --random)."""
    assert cfg.n_experts > 0
    rng = np.random.default_rng(seed)
    d, it, e = cfg.hidden_size, cfg.intermediate_size, cfg.n_experts

    def mat(*shape):
        return rng.normal(0, 0.02, size=shape).astype(np.float32)

    sd = {"wte.weight": mat(cfg.vocab_size, d),
          "wpe.weight": mat(cfg.max_position_embeddings, d),
          "ln_f.weight": np.ones(d, np.float32),
          "ln_f.bias": np.zeros(d, np.float32)}
    for i in range(cfg.num_hidden_layers):
        root = f"h.{i}."
        sd[root + "ln_1.weight"] = np.ones(d, np.float32)
        sd[root + "ln_1.bias"] = np.zeros(d, np.float32)
        sd[root + "attn.c_attn.weight"] = mat(d, 3 * d)
        sd[root + "attn.c_attn.bias"] = np.zeros(3 * d, np.float32)
        sd[root + "attn.c_proj.weight"] = mat(d, d)
        sd[root + "attn.c_proj.bias"] = np.zeros(d, np.float32)
        sd[root + "ln_2.weight"] = np.ones(d, np.float32)
        sd[root + "ln_2.bias"] = np.zeros(d, np.float32)
        sd[root + "moe.router.weight"] = mat(d, e)
        sd[root + "moe.router.bias"] = np.zeros(e, np.float32)
        sd[root + "moe.experts.c_fc.weight"] = mat(e, d, it)
        sd[root + "moe.experts.c_fc.bias"] = np.zeros((e, it), np.float32)
        sd[root + "moe.experts.c_proj.weight"] = mat(e, it, d)
        sd[root + "moe.experts.c_proj.bias"] = np.zeros((e, d), np.float32)
    return sd


def init_params(cfg: TransformerConfig, shard_config: ShardConfig,
                seed: int = 0, dtype=jnp.float32) -> Dict:
    """Random shard params with the same pytree structure as `load_params`."""
    rng = np.random.default_rng(seed)
    d, it = cfg.hidden_size, cfg.intermediate_size

    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.02, size=shape), dtype=dtype)

    def vec(n):
        return jnp.zeros((n,), dtype=dtype)

    def ln():
        return {"scale": jnp.ones((d,), dtype), "bias": vec(d)}

    def get_embed() -> Dict:
        return {"wte": mat(cfg.vocab_size, d),
                "wpe": mat(cfg.max_position_embeddings, d)}

    def get_block(block_id: int, subs: tuple) -> Dict:
        p: Dict = {}
        if 0 in subs:
            p["ln_before"] = ln()
            for name in ("q", "k", "v"):
                p[name] = {"w": mat(d, d), "b": vec(d)}
        if 1 in subs:
            p["attn_out"] = {"w": mat(d, d), "b": vec(d)}
        if 2 in subs:
            p["ln_after"] = ln()
            if cfg.n_experts:
                e = cfg.n_experts
                p["moe"] = {
                    "router": {"w": mat(d, e), "b": vec(e)},
                    "experts": {
                        "mlp_up": {"w": mat(e, d, it),
                                   "b": jnp.zeros((e, it), dtype)},
                        "mlp_down": {"w": mat(e, it, d),
                                     "b": jnp.zeros((e, d), dtype)},
                    },
                }
            else:
                p["mlp_up"] = {"w": mat(d, it), "b": vec(it)}
        if 3 in subs and not cfg.n_experts:
            p["mlp_down"] = {"w": mat(it, d), "b": vec(d)}
        return p

    def get_final() -> Dict:
        return {"ln": ln(), "head": {"w": mat(d, cfg.vocab_size),
                                     "b": vec(cfg.vocab_size)}}

    return build_shard_params(shard_config, get_embed, get_block, get_final)
