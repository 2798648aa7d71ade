"""Tensor parallelism: Megatron-style within-stage sharding of a block.

NEW capability beyond the reference (SURVEY.md §2.4: PipeEdge has no TP).
A transformer block's attention heads and MLP hidden dimension shard over a
mesh axis: q/k/v and MLP-up kernels column-split (no communication), the
attention-output and MLP-down kernels row-split, followed by one `psum` each
— the canonical 2-allreduce-per-block layout that keeps every matmul dense
on the local MXU.

Composes with the pipeline: a ('tp',)-sharded block runs inside one pipeline
stage, so a ('dp', 'stage', 'tp') mesh gives dp x pp x tp.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import jax_compat
from ..models.layers import (TransformerConfig, apply_causal_mask, gelu,
                             layer_norm)


# -- quantized TP collectives (trace-time flag, layers.set_fast_numerics
#    idiom): 0 = exact full-width psum; 4/8 = EQuARX-style quantized
#    allreduce (ops/qcollectives.py). Consumers must trace AFTER setting
#    it — make_tp_block_fn builds fresh per call, and SpmdPipeline keys
#    its compile cache on the current value.
_TP_QUANT_BITS = 0


def set_tp_quant_bits(bit: int) -> None:
    """Select the bitwidth of intra-stage TP/SP collectives (the
    runtime's --tp-quant-bits knob; docs/QUANT_COLLECTIVES.md)."""
    global _TP_QUANT_BITS  # pylint: disable=global-statement
    if bit not in (0, 4, 8):
        raise ValueError(f"tp quant bits must be 0, 4 or 8, got {bit}")
    _TP_QUANT_BITS = int(bit)


def get_tp_quant_bits() -> int:
    return _TP_QUANT_BITS


def tp_psum(x: jax.Array, axis: str) -> jax.Array:
    """THE allreduce of every Megatron block body here: exact psum at
    bits=0, quantized collective otherwise — the single gate the
    --tp-quant-bits knob flips for all six psum sites."""
    bit = _TP_QUANT_BITS
    if bit:
        from ..ops import qcollectives
        return qcollectives.qpsum(x, axis, bit)
    return jax.lax.psum(x, axis)


def _shard_by_specs(params: Dict, specs: Dict, mesh: Mesh,
                    axis: str) -> Dict:
    """Place a block's params per the SAME spec table shard_map uses as
    in_specs — one source of truth, so the placement can never drift from
    the compiled expectation (drift would silently reshard every call)."""
    specs = _rename_axis(specs, axis)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params,
        specs)


def shard_vit_block_params(params: Dict, mesh: Mesh, axis: str = "tp") -> Dict:
    """Place one ViT/DeiT block's params with Megatron TP sharding.

    Column-parallel (out-dim sharded): q/k/v, mlp_up. Row-parallel (in-dim
    sharded): attn_out, mlp_down. LayerNorms replicated.
    """
    return _shard_by_specs(params, _VIT_PARAM_SPECS, mesh, axis)


def _tp_block_local(p: Dict, x: jax.Array, cfg: TransformerConfig,
                    axis: str, act=gelu, causal: bool = False,
                    qkv_to_ctx=None, ffn_delta=None) -> jax.Array:
    """Per-device block body under shard_map: local head/hidden slices +
    two psums. `x` is replicated across the tp axis. Serves every pre-LN
    family: ViT/DeiT as-is, GPT-2 via act=gelu_new + causal=True.

    `qkv_to_ctx(q, k, v) -> ctx` ([b, s, h_local*hd]) overrides the
    attention core over the local heads — how KV-cache decoding plugs its
    cache-attend into this same projection/psum/MLP body
    (parallel/decode.py). `ffn_delta(p, normed) -> delta` replaces the
    dense Megatron MLP entirely — how the tp x ep MoE decode plugs the
    ep-sharded routed FFN under the tp-sharded attention
    (decode.make_tp_ep_stage_fns)."""
    n = jax.lax.axis_size(axis)
    heads_local = cfg.num_attention_heads // n
    b, s, d = x.shape
    hd = cfg.head_dim

    normed = layer_norm(p["ln_before"], x, cfg.layer_norm_eps)

    def proj(name):
        w = p[name]["w"]  # [D, D/n] local column slice
        y = jnp.dot(normed, w.astype(x.dtype),
                    preferred_element_type=jnp.float32) + p[name]["b"]
        return y.astype(x.dtype).reshape(b, s, heads_local, hd)

    q, k, v = proj("q"), proj("k"), proj("v")
    if qkv_to_ctx is not None:
        ctx = qkv_to_ctx(q, k, v)
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) / jnp.sqrt(
                                jnp.float32(hd))
        if causal:
            scores = apply_causal_mask(scores)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        ctx = ctx.reshape(b, s, heads_local * hd)
    # row-parallel output projection: partial products summed across devices
    attn = jnp.dot(ctx, p["attn_out"]["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    attn = tp_psum(attn, axis) + p["attn_out"]["b"]
    x = attn.astype(x.dtype) + x

    normed = layer_norm(p["ln_after"], x, cfg.layer_norm_eps)
    if ffn_delta is not None:
        return x + ffn_delta(p, normed)
    up = jnp.dot(normed, p["mlp_up"]["w"].astype(x.dtype),
                 preferred_element_type=jnp.float32) + p["mlp_up"]["b"]
    hidden = act(up.astype(x.dtype))
    down = jnp.dot(hidden, p["mlp_down"]["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    down = tp_psum(down, axis) + p["mlp_down"]["b"]
    return down.astype(x.dtype) + x


def shard_bert_block_params(params: Dict, mesh: Mesh, axis: str = "tp") \
        -> Dict:
    """Place one BERT (post-LN) block's params with Megatron TP sharding:
    same column/row layout as ViT, LayerNorms (attn_ln/out_ln) replicated."""
    return _shard_by_specs(params, _BERT_PARAM_SPECS, mesh, axis)


def family_tp_plan(cfg: TransformerConfig):
    """THE family dispatch point for tensor parallelism: returns
    (param spec table, per-device block body). Every TP consumer — the
    placement helpers here and the SPMD pipeline's stacked specs/block
    body — goes through this, so adding a family is one edit. MoE
    configs refuse here (the dense column/row kernel table does not
    describe a routed FFN) — the MoE composition lives in
    `family_tp_ep_plan`."""
    if cfg.n_experts:
        raise NotImplementedError(
            "Megatron TP does not cover MoE blocks (experts shard over "
            "'ep', not the column/row kernel table) — see family_tp_ep_plan "
            "/ decode.make_tp_ep_stage_fns for the tp x ep composition")
    if cfg.model_type == "bert":
        return _BERT_PARAM_SPECS, _tp_bert_block_local
    if cfg.model_type == "gpt2":
        from ..models.layers import gelu_new
        return _VIT_PARAM_SPECS, partial(_tp_block_local, act=gelu_new,
                                         causal=True)
    if cfg.model_type == "llama":
        return _LLAMA_PARAM_SPECS, _tp_llama_block_local
    return _VIT_PARAM_SPECS, _tp_block_local


def family_tp_ep_plan(cfg: TransformerConfig):
    """Family dispatch for the tp x ep MoE composition: returns
    (attention param spec table over 'tp', FFN activation). The attention
    half of an MoE block shards exactly like its dense family's attention
    (column q/k/v, row attn_out, replicated LNs); the routed FFN shards
    over 'ep' (parallel/expert.py). decode.make_tp_ep_stage_fns is the
    consumer — adding an MoE family is one edit HERE, mirroring
    family_tp_plan's single-dispatch-point contract."""
    if not cfg.n_experts:
        raise ValueError("family_tp_ep_plan requires an MoE config "
                         "(cfg.n_experts > 0); use family_tp_plan")
    if cfg.model_type == "gpt2":
        from ..models.layers import gelu_new
        return _VIT_PARAM_SPECS, gelu_new
    raise NotImplementedError(
        f"no tp x ep plan for MoE family {cfg.model_type!r}")


def shard_block_params(cfg: TransformerConfig, params: Dict, mesh: Mesh,
                       axis: str = "tp") -> Dict:
    """Megatron placement for one block's params (family-dispatched)."""
    specs, _ = family_tp_plan(cfg)
    return _shard_by_specs(params, specs, mesh, axis)


def _tp_bert_block_local(p: Dict, x: jax.Array, cfg: TransformerConfig,
                         axis: str) -> jax.Array:
    """Per-device BERT block body (post-LN residuals, bert.py sublayer
    semantics 0-3): attention on raw x, LayerNorm AFTER each residual."""
    n = jax.lax.axis_size(axis)
    heads_local = cfg.num_attention_heads // n
    b, s, _ = x.shape
    hd = cfg.head_dim

    def proj(name):
        w = p[name]["w"]  # [D, D/n] local column slice
        y = jnp.dot(x, w.astype(x.dtype),
                    preferred_element_type=jnp.float32) + p[name]["b"]
        return y.astype(x.dtype).reshape(b, s, heads_local, hd)

    q, k, v = proj("q"), proj("k"), proj("v")
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
                            jnp.float32(hd))
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    ctx = ctx.reshape(b, s, heads_local * hd)
    attn = jnp.dot(ctx, p["attn_out"]["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    attn = tp_psum(attn, axis) + p["attn_out"]["b"]
    x = layer_norm(p["attn_ln"], attn.astype(x.dtype) + x,
                   cfg.layer_norm_eps)

    up = jnp.dot(x, p["mlp_up"]["w"].astype(x.dtype),
                 preferred_element_type=jnp.float32) + p["mlp_up"]["b"]
    hidden = gelu(up.astype(x.dtype))
    down = jnp.dot(hidden, p["mlp_down"]["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    down = tp_psum(down, axis) + p["mlp_down"]["b"]
    return layer_norm(p["out_ln"], down.astype(x.dtype) + x,
                      cfg.layer_norm_eps)


def _tp_llama_block_local(p: Dict, x: jax.Array, cfg: TransformerConfig,
                          axis: str, qkv_to_ctx=None,
                          pos_ids=None) -> jax.Array:
    """Per-device llama block body (pre-RMSNorm, RoPE, GQA, SwiGLU).

    Column-sharded q/k/v keep GQA grouping local: shard i holds query
    heads [i*h/n, (i+1)*h/n) and kv heads [i*kv/n, (i+1)*kv/n), and query
    head g's kv head g//(h/kv) lands on the same shard, so the local
    grouped attend needs no collective. Requires heads, kv_heads, and
    intermediate_size divisible by the tp degree (reshapes fail loudly
    otherwise). Two psums per block, like every Megatron body here.

    `qkv_to_ctx(q, k, v) -> ctx` overrides the attention core over the
    local (RoPE'd) heads and `pos_ids` the rotation positions — how the
    llama KV-cached tp decode step plugs its cache-attend into this same
    projection/psum/SwiGLU body (models/llama.py tp_cached_block_step),
    mirroring _tp_block_local's hook for GPT-2."""
    from ..models.layers import rms_norm, rope_rotate
    from ..models.llama import _gqa_attend

    n = jax.lax.axis_size(axis)
    heads_local = cfg.num_attention_heads // n
    kv_local = cfg.kv_heads // n
    b, s, _ = x.shape
    hd = cfg.head_dim

    normed = rms_norm(p["ln_before"], x, cfg.layer_norm_eps)
    pos = jnp.arange(s) if pos_ids is None else pos_ids

    def proj(name, n_heads):
        y = jnp.dot(normed, p[name]["w"].astype(x.dtype),
                    preferred_element_type=jnp.float32) + p[name]["b"]
        return y.astype(x.dtype).reshape(b, s, n_heads, hd)

    q = rope_rotate(proj("q", heads_local), pos, cfg.rope_theta)
    k = rope_rotate(proj("k", kv_local), pos, cfg.rope_theta)
    v = proj("v", kv_local)
    ctx = (qkv_to_ctx(q, k, v) if qkv_to_ctx is not None
           else _gqa_attend(q, k, v, cfg))   # local heads, causal
    attn = jnp.dot(ctx, p["attn_out"]["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    attn = tp_psum(attn, axis) + p["attn_out"]["b"]
    x = attn.astype(x.dtype) + x

    normed = rms_norm(p["ln_after"], x, cfg.layer_norm_eps)
    gate = jnp.dot(normed, p["mlp_gate"]["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32) + p["mlp_gate"]["b"]
    up = jnp.dot(normed, p["mlp_up"]["w"].astype(x.dtype),
                 preferred_element_type=jnp.float32) + p["mlp_up"]["b"]
    hidden = jax.nn.silu(gate).astype(x.dtype) * up.astype(x.dtype)
    down = jnp.dot(hidden, p["mlp_down"]["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    down = tp_psum(down, axis) + p["mlp_down"]["b"]
    return down.astype(x.dtype) + x


_LLAMA_PARAM_SPECS = {
    "q": {"w": P(None, "tp"), "b": P("tp")},
    "k": {"w": P(None, "tp"), "b": P("tp")},
    "v": {"w": P(None, "tp"), "b": P("tp")},
    "attn_out": {"w": P("tp", None), "b": P()},
    "mlp_gate": {"w": P(None, "tp"), "b": P("tp")},
    "mlp_up": {"w": P(None, "tp"), "b": P("tp")},
    "mlp_down": {"w": P("tp", None), "b": P()},
    "ln_before": {"scale": P()},
    "ln_after": {"scale": P()},
}

_VIT_PARAM_SPECS = {
    "q": {"w": P(None, "tp"), "b": P("tp")},
    "k": {"w": P(None, "tp"), "b": P("tp")},
    "v": {"w": P(None, "tp"), "b": P("tp")},
    "attn_out": {"w": P("tp", None), "b": P()},
    "mlp_up": {"w": P(None, "tp"), "b": P("tp")},
    "mlp_down": {"w": P("tp", None), "b": P()},
    "ln_before": {"scale": P(), "bias": P()},
    "ln_after": {"scale": P(), "bias": P()},
}

_BERT_PARAM_SPECS = {
    "q": {"w": P(None, "tp"), "b": P("tp")},
    "k": {"w": P(None, "tp"), "b": P("tp")},
    "v": {"w": P(None, "tp"), "b": P("tp")},
    "attn_out": {"w": P("tp", None), "b": P()},
    "mlp_up": {"w": P(None, "tp"), "b": P("tp")},
    "mlp_down": {"w": P("tp", None), "b": P()},
    "attn_ln": {"scale": P(), "bias": P()},
    "out_ln": {"scale": P(), "bias": P()},
}


def _rename_axis(specs, axis):
    if axis == "tp":
        return specs
    return jax.tree_util.tree_map(
        lambda s: P(*(axis if a == "tp" else a for a in s)), specs,
        is_leaf=lambda s: isinstance(s, P))


def make_tp_block_fn(cfg: TransformerConfig, mesh: Mesh, axis: str = "tp"):
    """Jitted `fn(sharded_params, x) -> x` running one full transformer block
    with tensor parallelism over `axis`. `x` is replicated. Dispatches on
    the family: ViT/DeiT pre-LN blocks or BERT post-LN blocks."""
    specs, local = family_tp_plan(cfg)
    param_specs = _rename_axis(specs, axis)
    body = jax_compat.shard_map(partial(local, cfg=cfg, axis=axis),
                         mesh=mesh, in_specs=(param_specs, P()),
                         out_specs=P())
    return jax.jit(body)
