"""Model registry and shard factories (parity with /root/reference/model_cfg.py).

Same 9 supported models and layer counts (model_cfg.py:24-43), plus a
causal-decoder family (GPT-2/GPT-2-medium) the reference lacks; layer counts
are in sublayers (4 per transformer block). Unlike the reference, model
configs are local constants rather than `AutoConfig.from_pretrained` network
fetches (model_cfg.py:57-66), so everything works with zero egress; the
ViT-Huge num_labels=21843 override is baked in (model_cfg.py:62-66).
"""
from __future__ import annotations

import dataclasses
import io
import logging
import os
import struct
import zipfile
from collections.abc import Mapping
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from numpy.lib import format as npy_format

from .. import telemetry
from ..telemetry import metrics as prom
from . import ShardConfig
from .layers import TransformerConfig
from .shard import make_shard_fn, unstack_blocks
from . import bert as bert_mod
from . import brumby as brumby_mod
from . import deit as deit_mod
from . import gpt2 as gpt2_mod
from . import granite_hybrid as granite_hybrid_mod
from . import keye as keye_mod
from . import kimi as kimi_mod
from . import laguna as laguna_mod
from . import lfm2 as lfm2_mod
from . import mellum as mellum_mod
from . import llama as llama_mod
from . import minicpm_sala as minicpm_sala_mod
from . import nemotron_h as nemotron_h_mod
from . import qwen3_next as qwen3_next_mod
from . import vit as vit_mod

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class ModelEntry:
    name: str
    layers: int                  # sublayer count = 4 * blocks (a block of
    #                              one sublayer, nemotron_h's, counts four too)
    weights_file: str            # default npz filename (reference format)
    family: object               # module: vit_mod | bert_mod | deit_mod
    config: TransformerConfig


def _vit(name, layers, weights, hidden, blocks, heads, inter, labels,
         patch=16, img=224):
    return ModelEntry(name, layers, weights, vit_mod, TransformerConfig(
        model_type="vit", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, intermediate_size=inter, num_labels=labels,
        image_size=img, patch_size=patch))


def _bert(name, layers, weights, hidden, blocks, heads, inter, labels):
    return ModelEntry(name, layers, weights, bert_mod, TransformerConfig(
        model_type="bert", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, intermediate_size=inter, num_labels=labels,
        vocab_size=30522, max_position_embeddings=512))


def _deit(name, layers, weights, hidden, blocks, heads, inter):
    return ModelEntry(name, layers, weights, deit_mod, TransformerConfig(
        model_type="deit", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, intermediate_size=inter, num_labels=1000))


def _gpt2(name, layers, weights, hidden, blocks, heads, inter,
          vocab=50257, max_pos=1024, n_experts=0, capacity_factor=1.25):
    return ModelEntry(name, layers, weights, gpt2_mod, TransformerConfig(
        model_type="gpt2", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, intermediate_size=inter,
        layer_norm_eps=1e-5, vocab_size=vocab,
        max_position_embeddings=max_pos, n_experts=n_experts,
        capacity_factor=capacity_factor))


def _llama(name, layers, weights, hidden, blocks, heads, kv_heads, inter,
           vocab, max_pos, theta=10000.0, window=0):
    return ModelEntry(name, layers, weights, llama_mod, TransformerConfig(
        model_type="llama", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, num_kv_heads=kv_heads,
        intermediate_size=inter, layer_norm_eps=1e-5, vocab_size=vocab,
        max_position_embeddings=max_pos, rope_theta=theta,
        sliding_window=window))


def _keye(name, weights, hidden, blocks, heads, kv_heads, head_dim, vocab,
          max_pos, experts, expert_width, per_tok, index, mrope,
          theta=1e7):
    index_heads, index_head_dim, topk, q_chunk = index
    return ModelEntry(name, 4 * blocks, weights, keye_mod, TransformerConfig(
        model_type="keye", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, num_kv_heads=kv_heads,
        attn_head_dim=head_dim, intermediate_size=0, layer_norm_eps=1e-6,
        vocab_size=vocab, max_position_embeddings=max_pos, rope_theta=theta,
        n_experts=experts, moe_intermediate_size=expert_width,
        num_experts_per_tok=per_tok, norm_topk_prob=True, qk_norm=True,
        mrope_section=tuple(mrope), index_heads=index_heads,
        index_head_dim=index_head_dim, index_topk=topk,
        index_q_chunk=q_chunk))


def _kimi(name, weights, hidden, blocks, heads, mla, dense_width, vocab,
          max_pos, experts, expert_width, per_tok, span):
    q_rank, kv_rank, nope, rope, v_dim = mla
    return ModelEntry(name, 4 * blocks, weights, kimi_mod, TransformerConfig(
        model_type="kimi", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, intermediate_size=dense_width,
        layer_norm_eps=1e-6, vocab_size=vocab,
        max_position_embeddings=max_pos, rope_theta=50000.0,
        rope_yarn=(32.0, 4096, 1.0, 1.0, 1.0, 1.0), n_experts=experts,
        moe_intermediate_size=expert_width, num_experts_per_tok=per_tok,
        norm_topk_prob=True, router="sigmoid", routed_scaling_factor=2.827,
        gate_sum_eps=1e-20, n_shared_experts=1, first_k_dense=1, q_lora_rank=q_rank,
        kv_lora_rank=kv_rank, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=v_dim, prefill_chunk=span))


def _qwen3_next(name, weights, hidden, blocks, heads, kv_heads, head_dim,
                linear, vocab, max_pos, experts, expert_width, per_tok, span):
    key_heads, value_heads, key_dim, value_dim, chunk = linear
    return ModelEntry(name, 4 * blocks, weights, qwen3_next_mod,
                      TransformerConfig(
        model_type="qwen3_next", hidden_size=hidden,
        num_hidden_layers=blocks, num_attention_heads=heads,
        num_kv_heads=kv_heads, attn_head_dim=head_dim, intermediate_size=0,
        layer_norm_eps=1e-6, vocab_size=vocab,
        max_position_embeddings=max_pos, rope_theta=1e7,
        partial_rotary_factor=0.25, n_experts=experts,
        moe_intermediate_size=expert_width, num_experts_per_tok=per_tok,
        norm_topk_prob=True, n_shared_experts=1, full_attention_interval=4,
        linear_key_heads=key_heads, linear_value_heads=value_heads,
        linear_key_dim=key_dim, linear_value_dim=value_dim,
        linear_conv_kernel=4, linear_chunk=chunk, prefill_chunk=span))


def _lfm2(name, weights, hidden, layer_types, heads, kv_heads, dense_width,
          vocab, max_pos, experts, expert_width, per_tok, span):
    blocks = len(layer_types)
    return ModelEntry(name, 4 * blocks, weights, lfm2_mod, TransformerConfig(
        model_type="lfm2", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, num_kv_heads=kv_heads,
        intermediate_size=dense_width, layer_norm_eps=1e-5, vocab_size=vocab,
        max_position_embeddings=max_pos, rope_theta=1e6, qk_norm=True,
        n_experts=experts, moe_intermediate_size=expert_width,
        num_experts_per_tok=per_tok, norm_topk_prob=True, router="sigmoid",
        routed_scaling_factor=1.0, gate_sum_eps=1e-6, first_k_dense=2,
        layer_types=tuple(layer_types), conv_kernel=3, prefill_chunk=span))


def _laguna(name, weights, hidden, pattern, heads, kv_heads, head_dim,
            window, dense_width, vocab, max_pos, experts, expert_width,
            per_tok, yarn, sliding_theta, span, theta=500000.0):
    full_heads, sliding_heads = heads
    blocks = len(pattern)
    return ModelEntry(name, 4 * blocks, weights, laguna_mod,
                      TransformerConfig(
        model_type="laguna", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=max(heads), num_kv_heads=kv_heads,
        attn_head_dim=head_dim, intermediate_size=dense_width,
        layer_norm_eps=1e-6, vocab_size=vocab,
        max_position_embeddings=max_pos, rope_theta=theta,
        rope_yarn=tuple(yarn), partial_rotary_factor=0.5,
        sliding_rope_theta=sliding_theta, sliding_window=window,
        qk_norm=True, n_experts=experts, moe_intermediate_size=expert_width,
        num_experts_per_tok=per_tok, norm_topk_prob=True,
        routed_scaling_factor=2.5, n_shared_experts=1, first_k_dense=1,
        layer_types=_layer_types(pattern),
        layer_heads=tuple({"f": full_heads, "s": sliding_heads}[m]
                          for m in pattern),
        head_gate=True, prefill_chunk=span))


def _mellum(name, weights, hidden, pattern, heads, kv_heads, head_dim,
            window, vocab, max_pos, experts, expert_width, per_tok, yarn,
            span, theta=500000.0):
    """laguna's block (`models/mellum.py`): one head count and one base for
    both kinds of layer, the whole head turned, no gate a head, no shared
    expert, no dense layer, no scaling factor."""
    blocks = len(pattern)
    return ModelEntry(name, 4 * blocks, weights, mellum_mod,
                      TransformerConfig(
        model_type="mellum", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, num_kv_heads=kv_heads,
        attn_head_dim=head_dim, intermediate_size=0, layer_norm_eps=1e-6,
        vocab_size=vocab, max_position_embeddings=max_pos, rope_theta=theta,
        rope_yarn=tuple(yarn), partial_rotary_factor=1.0,
        sliding_rope_theta=theta, sliding_window=window, qk_norm=True,
        n_experts=experts, moe_intermediate_size=expert_width,
        num_experts_per_tok=per_tok, norm_topk_prob=True,
        layer_types=_layer_types(pattern),
        layer_heads=(heads,) * blocks, prefill_chunk=span))


def _minicpm_sala(name, weights, hidden, pattern, heads, kv_heads, head_dim,
                  dense_width, vocab, max_pos, sparse, chunk, span,
                  scale_emb=12.0, scale_depth=1.4, base=256):
    blocks = len(pattern)
    return ModelEntry(name, 4 * blocks, weights, minicpm_sala_mod,
                      TransformerConfig(
        model_type="minicpm_sala", hidden_size=hidden,
        num_hidden_layers=blocks, num_attention_heads=heads,
        num_kv_heads=kv_heads, attn_head_dim=head_dim,
        intermediate_size=dense_width, layer_norm_eps=1e-6, vocab_size=vocab,
        max_position_embeddings=max_pos, rope_theta=10000.0, qk_norm=True,
        layer_types=_layer_types(pattern), linear_chunk=chunk,
        prefill_chunk=span, scale_emb=scale_emb, scale_depth=scale_depth,
        dim_model_base=base, published_layers=blocks,
        sparse_attention=tuple(sparse)))


def _nemotron_h(name, weights, hidden, pattern, heads, kv_heads, head_dim,
                ssm, vocab, max_pos, experts, expert_width, latent,
                shared_width, per_tok, span):
    ssm_heads, ssm_head_dim, state, groups, conv, chunk = ssm
    blocks = len(pattern)
    return ModelEntry(name, 4 * blocks, weights, nemotron_h_mod,
                      TransformerConfig(
        model_type="nemotron_h", hidden_size=hidden,
        num_hidden_layers=blocks, num_attention_heads=heads,
        num_kv_heads=kv_heads, attn_head_dim=head_dim, intermediate_size=0,
        layer_norm_eps=1e-5, vocab_size=vocab,
        max_position_embeddings=max_pos, layer_types=_layer_types(pattern),
        ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim, ssm_state=state,
        ssm_groups=groups, conv_kernel=conv, linear_chunk=chunk,
        n_experts=experts, moe_intermediate_size=expert_width,
        moe_latent_size=latent, shared_expert_width=shared_width,
        num_experts_per_tok=per_tok, norm_topk_prob=True, router="sigmoid",
        routed_scaling_factor=5.0, gate_sum_eps=1e-20, n_shared_experts=1,
        expert_act="relu2", prefill_chunk=span))


def _granite_hybrid(name, weights, hidden, pattern, heads, kv_heads, head_dim,
                    ssm, dense_width, vocab, max_pos, multipliers, span):
    ssm_heads, ssm_head_dim, state, groups, conv, chunk = ssm
    embedding, residual, attention, logits = multipliers
    blocks = len(pattern)
    return ModelEntry(name, 4 * blocks, weights, granite_hybrid_mod,
                      TransformerConfig(
        model_type="granite_hybrid", hidden_size=hidden,
        num_hidden_layers=blocks, num_attention_heads=heads,
        num_kv_heads=kv_heads, attn_head_dim=head_dim,
        intermediate_size=dense_width, layer_norm_eps=1e-5, vocab_size=vocab,
        max_position_embeddings=max_pos, layer_types=_layer_types(pattern),
        ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim, ssm_state=state,
        ssm_groups=groups, conv_kernel=conv, linear_chunk=chunk,
        scale_emb=embedding, residual_multiplier=residual,
        attention_multiplier=attention, logits_scaling=logits,
        prefill_chunk=span))


def _brumby(name, weights, hidden, blocks, heads, kv_heads, head_dim,
            dense_width, vocab, max_pos, chunk, span, theta=1e6):
    return ModelEntry(name, 4 * blocks, weights, brumby_mod,
                      TransformerConfig(
        model_type="brumby", hidden_size=hidden, num_hidden_layers=blocks,
        num_attention_heads=heads, num_kv_heads=kv_heads,
        attn_head_dim=head_dim, intermediate_size=dense_width,
        layer_norm_eps=1e-6, vocab_size=vocab,
        max_position_embeddings=max_pos, rope_theta=theta, qk_norm=True,
        linear_chunk=chunk, prefill_chunk=span))


# a pattern of mixers, one letter a block. LFM2's: c a gated short
# convolution, a grouped-query attention (no interval: the last attention
# comes early). Laguna's: f attention over every position, s over a window.
# MiniCPM-SALA's: m MiniCPM4's block-sparse attention, l lightning attention.
# Nemotron-H's, the published `hybrid_override_pattern` as it is, a letter a
# SUBLAYER: M a Mamba-2 mixer, * an attention, E an expert layer. Granite
# 4.0-H's, its published `layer_types` in Nemotron-H's letters: M a Mamba-2
# block, * an attention block, each with its SwiGLU
def _layer_types(pattern: str) -> tuple:
    return tuple({"c": "conv", "a": "full_attention", "f": "full_attention",
                  "s": "sliding_attention", "m": "minicpm4",
                  "l": "lightning-attn", "M": "mamba", "*": "attention",
                  "E": "experts"}[m] for m in pattern)


_MODELS: Dict[str, ModelEntry] = {e.name: e for e in [
    _vit("google/vit-base-patch16-224", 48, "ViT-B_16-224.npz", 768, 12, 12, 3072, 1000),
    _vit("google/vit-large-patch16-224", 96, "ViT-L_16-224.npz", 1024, 24, 16, 4096, 1000),
    _vit("google/vit-huge-patch14-224-in21k", 128, "ViT-H_14.npz", 1280, 32, 16, 5120,
         21843, patch=14),
    _bert("bert-base-uncased", 48, "BERT-B.npz", 768, 12, 12, 3072, 0),
    _bert("bert-large-uncased", 96, "BERT-L.npz", 1024, 24, 16, 4096, 0),
    _bert("textattack/bert-base-uncased-CoLA", 48, "BERT-B-CoLA.npz", 768, 12, 12, 3072, 2),
    _deit("facebook/deit-base-distilled-patch16-224", 48, "DeiT_B_distilled.npz",
          768, 12, 12, 3072),
    _deit("facebook/deit-small-distilled-patch16-224", 48, "DeiT_S_distilled.npz",
          384, 12, 6, 1536),
    _deit("facebook/deit-tiny-distilled-patch16-224", 48, "DeiT_T_distilled.npz",
          192, 12, 3, 768),
    # causal-decoder family: beyond the reference's encoder-only list
    _gpt2("gpt2", 48, "GPT2.npz", 768, 12, 12, 3072),
    _gpt2("gpt2-medium", 96, "GPT2-M.npz", 1024, 24, 16, 4096),
    # synthetic switch-MoE decoder (top-1 routed FFN, 8 experts/block)
    _gpt2("pipeedge/gpt2-moe-8e", 48, "GPT2-MoE-8E.npz", 768, 12, 12, 3072,
          n_experts=8),
    # llama family: RoPE / RMSNorm / SwiGLU / grouped-query attention
    _llama("meta-llama/Llama-2-7b-hf", 128, "Llama-2-7B.npz", 4096, 32, 32,
           32, 11008, vocab=32000, max_pos=4096),
    _llama("meta-llama/Meta-Llama-3-8B", 128, "Llama-3-8B.npz", 4096, 32,
           32, 8, 14336, vocab=128256, max_pos=8192, theta=500000.0),
    # Mistral = the llama block with sliding-window attention (identical
    # HF state-dict layout; the window is a mask, not a weight change)
    _llama("mistralai/Mistral-7B-v0.1", 128, "Mistral-7B.npz", 4096, 32,
           32, 8, 14336, vocab=32000, max_pos=32768, window=4096),
    # Keye-VL-2.0's language model: Qwen3-MoE blocks (128 experts, 8 a
    # token, no drops) whose attention reads a learned top-2048 selection
    _keye("Kwai-Keye/Keye-VL-2.0-30B-A3B", "Keye-VL-2.0-30B-A3B.npz", 2048,
          48, 32, 4, 128, vocab=151936, max_pos=262144, experts=128,
          expert_width=768, per_tok=8, index=(16, 64, 2048, 512),
          mrope=(16, 24, 24)),
    # Kimi-K2: DeepSeek-V3's block (latent attention, a leading dense layer,
    # 384 experts routed 8 a token by a sigmoid beside a shared one). One
    # chip holds a share of it: `...@5,e0+12,v20480` (get_model_entry)
    _kimi("moonshotai/Kimi-K2-Instruct", "Kimi-K2-Instruct.npz", 7168, 61,
          64, (1536, 512, 128, 64, 128), 18432, vocab=163840,
          max_pos=131072, experts=384, expert_width=2048, per_tok=8,
          span=128),
    # Qwen3-Next: periods of three Gated DeltaNet layers (a state a head,
    # no keys and values) and one gated full-attention layer, 512 small
    # experts routed 10 a token beside a gated shared one. One chip holds a
    # share of one period: `...@4,e0+256,v75968`
    _qwen3_next("Qwen/Qwen3-Next-80B-A3B-Instruct",
                "Qwen3-Next-80B-A3B-Instruct.npz", 2048, 48, 16, 2, 256,
                (16, 32, 128, 128, 64), vocab=151936, max_pos=262144,
                experts=512, expert_width=512, per_tok=10, span=1024),
    # LFM2-8B-A1B: 18 gated short convolutions (a state of two positions a
    # request) and 6 GQA layers, two leading dense FFNs, then 32 experts
    # routed 4 a token by a sigmoid, none shared; the head is the embedding.
    # One chip holds one of two pipeline stages: `...@12`
    _lfm2("LiquidAI/LFM2-8B-A1B", "LFM2-8B-A1B.npz", 2048,
          _layer_types("ccacccacccacccacccaccacc"), 32, 8, 7168, vocab=65536,
          max_pos=128000, experts=32, expert_width=1792, per_tok=4, span=128),
    # Laguna-XS.2: periods of one full-attention layer (48 query heads,
    # YaRN on half of a head's lanes) and three that attend the last 512
    # positions (64 query heads, plain rotation) and keep a ring of them;
    # one leading dense FFN, then 256 experts routed 8 a token by a softmax
    # beside a gated shared one. One chip holds the first of eight pipeline
    # stages: `...@5`
    _laguna("poolside/Laguna-XS.2", "Laguna-XS.2.npz", 2048, "fsss" * 10,
            (48, 64), 8, 128, window=512, dense_width=8192, vocab=100352,
            max_pos=262144, experts=256, expert_width=512, per_tok=8,
            yarn=(64.0, 4096, 64.0, 1.0, 1.4158883083359672),
            sliding_theta=10000.0, span=128),
    # Mellum2-12B-A2.5B: periods of three layers that attend the last 1,024
    # positions and one full-attention layer under YaRN, 32 query and 4 KV
    # heads of 128 in both, the whole head turned; every FFN 64 experts of
    # 896 routed 8 a token by a renormalised softmax, none shared, no dense
    # layer. One chip holds the first of four pipeline stages, two periods:
    # `...@8`
    _mellum("JetBrains/Mellum2-12B-A2.5B-Instruct",
            "Mellum2-12B-A2.5B-Instruct.npz", 2304, "sssf" * 7, 32, 4, 128,
            window=1024, vocab=98304, max_pos=131072, experts=64,
            expert_width=896, per_tok=8,
            yarn=(16.0, 8192, 32.0, 1.0, 1.2772588722239782), span=512),
    # MiniCPM-SALA: 8 layers of MiniCPM4's block-sparse attention (32 query
    # and 2 KV heads, no rotation; pooled keys of 32 positions every 16,
    # blocks of 64: the first, the 32 local and the 64 best) among 24 of
    # lightning attention (a state of 128 x 128 a head, 32 heads), a dense
    # SwiGLU in every layer, MiniCPM's scalings. One chip holds the first of
    # eight pipeline stages, one period: `...@4`
    _minicpm_sala("openbmb/MiniCPM-SALA", "MiniCPM-SALA.npz", 4096,
                  "m" + "l" * 8 + "m" + "l" * 6 + "mm" + "l" * 4 + "m"
                  + "l" * 6 + "mmm", 32, 2, 128, dense_width=16384,
                  vocab=73448, max_pos=524288,
                  sparse=(32, 16, 64, 64, 1, 2048, 8192), chunk=128,
                  span=1024),
    # Nemotron-3-Super: 88 layers of ONE sublayer each, 40 Mamba-2 mixers
    # (128 heads of 64, a state of 128 a lane, 8 groups of B and C), 8 plain
    # attentions (32 query and 2 KV heads of 128, no rotation) and 40 expert
    # layers (512 experts of 2,688 in a 1,024-wide latent, 22 a token by a
    # sigmoid, beside a shared one of 5,376; relu squared, no gate matrix).
    # One chip holds a quarter of each expert layer in the first of eight
    # pipeline stages, one period: `...@11,e0+128,v32768`
    _nemotron_h("nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16",
                "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.npz", 4096,
                "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                "EMEMEMEMEM*EMEMEMEM*EMEMEMEME", 32, 2, 128,
                ssm=(128, 64, 128, 8, 4, 128), vocab=131072, max_pos=262144,
                experts=512, expert_width=2688, latent=1024,
                shared_width=5376, per_tok=22, span=64),
    # granite-4.0-h-micro: 40 blocks of a mixer and a SwiGLU of 8,192, 36
    # Mamba-2 mixers (64 heads of 64, a state of 128 a lane, ONE group of B
    # and C) and 4 plain attentions (32 query and 8 KV heads of 64, no
    # rotation) at blocks 5, 15, 25 and 35; the embedding times 12, the
    # residual branches times 0.22, the scores times 1/64, the logits over
    # 8; the head is the embedding. One chip holds it whole (6.38 GB)
    _granite_hybrid("ibm-granite/granite-4.0-h-micro",
                    "granite-4.0-h-micro.npz", 2048,
                    "MMMMM*" + "MMMMMMMMM*" * 3 + "MMMM", 32, 8, 64,
                    ssm=(64, 64, 128, 1, 4, 256), dense_width=8192,
                    vocab=100352, max_pos=131072,
                    multipliers=(12.0, 0.22, 0.015625, 8.0), span=64),
    # Brumby-14B-Base: Qwen3-14B's trunk (40 blocks, a SwiGLU of 17,408, two
    # tables of 151,936) with every attention a power-retention layer: 40
    # query and 8 KV heads of 128, q/k norms, rotation, a gate a KV head, a
    # state of 128 x 8,320 a KV head (34 MB a request a layer) and no keys
    # or values at all. One chip holds the first of four pipeline stages
    # with both tables: `...@10`
    _brumby("manifestai/Brumby-14B-Base", "Brumby-14B-Base.npz", 5120, 40, 40,
            8, 128, dense_width=17408, vocab=151936, max_pos=32768, chunk=128,
            span=256),
    # tiny synthetic models for fast tests / CI (not in the reference's list)
    _vit("pipeedge/test-tiny-vit", 8, "test-tiny-vit.npz", 32, 2, 4, 64, 5,
         patch=4, img=16),
    _bert("pipeedge/test-tiny-bert", 8, "test-tiny-bert.npz", 32, 2, 4, 64, 2),
    _gpt2("pipeedge/test-tiny-gpt2", 8, "test-tiny-gpt2.npz", 32, 2, 4, 64,
          vocab=100, max_pos=64),
    _llama("pipeedge/test-tiny-llama", 8, "test-tiny-llama.npz", 32, 2, 4,
           2, 64, vocab=100, max_pos=64),
    _llama("pipeedge/test-tiny-mistral", 8, "test-tiny-mistral.npz", 32, 2,
           4, 2, 64, vocab=100, max_pos=64, window=4),
    # capacity_factor = n_experts -> no capacity drops: routing is then a
    # pure per-token top-1 gate, which is causal and batch-size-invariant,
    # so cached decode and split pipelines match the full forward exactly
    # (capacity-bounded models trade that exactness for bounded compute)
    _keye("pipeedge/test-tiny-keye", "test-tiny-keye.npz", 32, 2, 4, 2, 16,
          vocab=100, max_pos=64, experts=8, expert_width=16, per_tok=2,
          index=(2, 8, 4, 8), mrope=(2, 3, 3)),
    _kimi("pipeedge/test-tiny-kimi", "test-tiny-kimi.npz", 32, 3, 4,
          (24, 16, 8, 8, 8), 64, vocab=100, max_pos=64, experts=8,
          expert_width=16, per_tok=2, span=8),
    # eight blocks: each kind of block has two runs in one stage
    _qwen3_next("pipeedge/test-tiny-qwen3-next", "test-tiny-qwen3-next.npz",
                32, 8, 4, 2, 16, (2, 4, 8, 8, 4), vocab=100, max_pos=64,
                experts=8, expert_width=16, per_tok=2, span=8),
    # eight blocks in the published order: two kinds of convolution block
    # (the leading dense pair, then routed) around two attention blocks
    _lfm2("pipeedge/test-tiny-lfm2", "test-tiny-lfm2.npz", 32,
          _layer_types("ccacccac"), 4, 2, 64, vocab=100, max_pos=64,
          experts=8, expert_width=16, per_tok=2, span=8),
    # six blocks: the dense full block, a period's three window blocks, a
    # routed full block and a window block after it (two runs share the
    # rings); the ramp of its YaRN has a frequency halfway
    _laguna("pipeedge/test-tiny-laguna", "test-tiny-laguna.npz", 32,
            "fsssfs", (4, 6), 2, 16, window=8, dense_width=64, vocab=100,
            max_pos=64, experts=8, expert_width=16, per_tok=2,
            yarn=(4.0, 16, 2.0, 0.25, 1.1386294361119891),
            sliding_theta=100.0, span=4, theta=10000.0),
    # eight blocks, two periods: every ring wraps past 8 positions, the
    # spans of 4 are half a ring, the ramp of its YaRN has frequencies
    # inside it; 3 of 8 experts a token
    _mellum("pipeedge/test-tiny-mellum", "test-tiny-mellum.npz", 32,
            "sssf" * 2, 4, 2, 16, window=8, vocab=100, max_pos=64,
            experts=8, expert_width=16, per_tok=3,
            yarn=(4.0, 16, 2.0, 0.25, 1.1386294361119891), span=4,
            theta=10000.0),
    # six blocks: either kind has two runs in one stage; kernels of 4
    # every 2, blocks of 8 (the first, the 2 local and the 2 best), dense
    # up to 32 positions
    _minicpm_sala("pipeedge/test-tiny-minicpm-sala",
                  "test-tiny-minicpm-sala.npz", 32, "mlllml", 4, 2, 8,
                  dense_width=64, vocab=100, max_pos=128,
                  sparse=(4, 2, 8, 2, 1, 16, 32), chunk=4, span=8),
    # eight blocks of one sublayer: a run of two Mamba-2 blocks among runs
    # of one, three expert runs, the attention between them
    _nemotron_h("pipeedge/test-tiny-nemotron-h", "test-tiny-nemotron-h.npz",
                32, "MEMM*EME", 4, 2, 8, ssm=(4, 8, 8, 2, 4, 4), vocab=100,
                max_pos=64, experts=8, expert_width=16, latent=16,
                shared_width=24, per_tok=3, span=8),
    # eight blocks of a mixer and a SwiGLU: Mamba-2 runs of two, three and
    # one around two attention blocks (the first not first in the stage),
    # ONE group of four heads, the scores at 1 / head_dim
    _granite_hybrid("pipeedge/test-tiny-granite-hybrid",
                    "test-tiny-granite-hybrid.npz", 32, "MM*MMM*M", 4, 2, 8,
                    ssm=(4, 8, 8, 1, 4, 4), dense_width=64, vocab=100,
                    max_pos=64, multipliers=(12.0, 0.22, 0.125, 8.0), span=8),
    # four blocks, all alike: two KV heads of two query heads of 8 (a state
    # of 8 x 40 a KV head), chunks of 4 in spans of 6: a span's last chunk
    # is short
    _brumby("pipeedge/test-tiny-brumby", "test-tiny-brumby.npz", 32, 4, 4, 2,
            8, dense_width=64, vocab=100, max_pos=64, chunk=4, span=6),
    _gpt2("pipeedge/test-tiny-moe", 8, "test-tiny-moe.npz", 32, 2, 4, 64,
          vocab=100, max_pos=64, n_experts=4, capacity_factor=4.0),
]}


def get_model_names() -> List[str]:
    """Available model names (model_cfg.py:45-47)."""
    return list(_MODELS.keys())


def get_model_entry(model_name: str) -> ModelEntry:
    """The entry of `model_name`. `<name>@<cut>` is the same model cut to
    what one chip or one stage holds of it, with its embedding, final norm
    and head, as a rule and not as entries of their own: `<cut>` is comma
    separated, `<blocks>` (its first blocks: the depth cut a benchmark or a
    stage-sized deployment runs), `e<first>+<count>` (of each expert
    layer's experts the `count` from `first`: the share of one of the chips
    a deployment divides a layer over; the router keeps its width) and
    `v<rows>` (the first rows of the vocabulary, in embedding and head). A
    cut's `layers` counts four sublayers a block, as `-pt` numbers them, also
    where a block is one sublayer (the nemotron_h family, whose blocks are
    the letters of its pattern): a partition names such a block by its four
    numbers and the family takes it whole."""
    name, at, cut = model_name.partition("@")
    entry = _MODELS[name]
    if not at:
        return entry
    cfg, layers = entry.config, entry.layers
    for part in cut.split(","):
        try:
            if part[:1] == "e":
                held = tuple(int(n) for n in part[1:].split("+"))
                first, count = held
                if not (cfg.num_experts_per_tok and first >= 0 and count >= 1
                        and first + count <= cfg.n_experts):
                    raise ValueError
                cfg = dataclasses.replace(cfg, held_experts=held)
            elif part[:1] == "v":
                if not 1 <= int(part[1:]) <= cfg.vocab_size:
                    raise ValueError
                cfg = dataclasses.replace(cfg, vocab_size=int(part[1:]))
            else:
                if not 1 <= int(part) <= cfg.num_hidden_layers:
                    raise ValueError
                cfg = dataclasses.replace(cfg, num_hidden_layers=int(part))
                layers = 4 * int(part)
        except ValueError:
            raise ValueError(
                f"{model_name}: {name} has {entry.config.num_hidden_layers} "
                f"blocks, {entry.config.n_experts} routed experts and a "
                f"vocabulary of {entry.config.vocab_size}; no cut "
                f"{part!r}") from None
    stem, ext = os.path.splitext(entry.weights_file)
    return dataclasses.replace(entry, name=model_name, layers=layers,
                               weights_file=f"{stem}@{cut}{ext}", config=cfg)


def decoder_model(model_name: str) -> str:
    """argparse `type=` of the decoding CLIs: a registered causal decoder,
    whole or as `<name>@<cut>`."""
    try:
        known = get_model_entry(model_name).family.FAMILY.decoder_model
    except (KeyError, ValueError):
        known = False
    if not known:
        raise ValueError(f"{model_name!r} is no registered decoder")
    return model_name


def get_model_layers(model_name: str) -> int:
    """Total sublayer count (model_cfg.py:53-55)."""
    return get_model_entry(model_name).layers


def get_model_config(model_name: str) -> TransformerConfig:
    """Static config (model_cfg.py:57-66, without the network fetch)."""
    return get_model_entry(model_name).config


def get_model_default_weights_file(model_name: str) -> str:
    """Default weights filename (model_cfg.py:68-70)."""
    return get_model_entry(model_name).weights_file


def make_shard_config(model_name: str, layer_start: int, layer_end: int) -> ShardConfig:
    """is_first/is_last derived from the global layer range (model_cfg.py:87-90)."""
    return ShardConfig(layer_start=layer_start, layer_end=layer_end,
                       is_first=layer_start == 1,
                       is_last=layer_end == get_model_layers(model_name))


# The deepest stage whose full blocks the host driver's programs run unrolled:
# every registered model's depth. On the v5e six unrolled ViT-Large blocks
# take 2.083 ms where a scan over their stack takes 2.360 (PR 30's pair, the
# driver's; shard.shard_apply has the reason), and no cell, test or job ever
# asked for another limit.
UNROLL_BLOCKS = 48


def should_unroll_blocks(n_blocks: int) -> bool:
    """Execution-layout policy of the host driver's stage programs: unroll
    full blocks when the depth is within `UNROLL_BLOCKS`. The SPMD driver
    always unrolls (parallel/spmd.py::run_blocks) and passes
    `module_shard_factory(unroll=False)` for the stacked layout it takes."""
    return 0 < n_blocks <= UNROLL_BLOCKS


_LOCAL_HEADER = struct.Struct("<4s22xHH")    # signature, name and extra lengths
_MEMBERS = prom.REGISTRY.counter(
    "pipeedge_weights_members_total",
    "members of a weights file handed to a loader: mapped (a view of the "
    "file's pages) or read (np.load's copy: a compressed, Fortran-order or "
    "object member)")
for _path in ("mapped", "read"):
    _MEMBERS.declare(path=_path)


class _TimedReads(Mapping):
    """A weights file (`.npz`) as a family's `load_params` is handed it
    (`weights`): a member is a read-only view of ONE `np.memmap` of the
    file, found through the zip's directory, its local header and the
    `.npy` header behind it. Nothing is read until a view is copied, so a
    byte of an expert goes from the file's pages into the leaf it belongs
    to (`decoder.on_device`) and nowhere else on the host; `np.load` takes
    a stored member in pieces with a CRC32 over every byte and hands back a
    copy (0.45-0.7 GB/s on the builders' host, PERF.md section 6, PR 35).

    What decides is what the file shows, a member at a time: one that is
    `ZIP_STORED` (`np.savez`'s), unencrypted, C-ordered and holds no
    objects is mapped; any other (`np.savez_compressed`'s, a Fortran-order
    or object array, a `.npy` version without a public header reader) is
    read with `np.load` as before, CRC and all.
    `pipeedge_weights_members_total{path}` says which. Either way the
    member's bytes add to `pipeedge_startup_bytes_total{phase=
    "weights_read"}` as it is handed out, each once; the phase's seconds
    are the directory, the headers, a read member's read, and the copies
    out of the map that `decoder.on_device` makes (a family that hands a
    view straight to `jnp.asarray` has its pages read inside that transfer,
    in `weights_place`).

    The map is the block's: `__exit__` lets go of it, and it is unmapped
    with the last view of it. A view is never a parameter, though: where
    the CPU backend would keep one as its own buffer (it does that to a
    host array on a 64-byte boundary), the member is copied out here, or a
    file rewritten in place would change a live model (a slice of a view
    that a family hands over unconverted can still fall on such a boundary:
    the file of a live CPU model is not to be rewritten in place)."""

    def __init__(self, path: str):
        with telemetry.startup("weights_read"):
            self._path = path
            self._read = None       # np.load's file, for what is not mapped
            self._file = open(path, "rb")
            try:
                with zipfile.ZipFile(self._file) as directory:
                    self._members = {
                        info.filename.removesuffix(".npy"): info
                        for info in directory.infolist()}
                self._map = np.asarray(np.memmap(self._file, np.uint8, "r"))
            except (OSError, ValueError, zipfile.BadZipFile):
                self._file.close()
                raise

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._file.close()
        if self._read is not None:
            self._read.close()
        self._map = None

    def _view(self, info: zipfile.ZipInfo):
        """The member `info` as a view of the map, or None where the file
        does not hold it as an array's own bytes in C order."""
        if info.compress_type != zipfile.ZIP_STORED or info.flag_bits & 1 \
                or not info.filename.endswith(".npy"):
            return None
        # the directory has no offset of the data: the local header's name
        # and extra field (NumPy writes a zip64 one) come first
        self._file.seek(info.header_offset)
        magic, name, extra = _LOCAL_HEADER.unpack(
            self._file.read(_LOCAL_HEADER.size))
        if magic != zipfile.stringFileHeader:
            return None
        start = self._file.seek(name + extra, os.SEEK_CUR)
        header_of = {(1, 0): npy_format.read_array_header_1_0,
                     (2, 0): npy_format.read_array_header_2_0}.get(
            npy_format.read_magic(self._file))
        if header_of is None:
            return None
        shape, fortran_order, dtype = header_of(self._file)
        at = self._file.tell()
        nbytes = dtype.itemsize * int(np.prod(shape, dtype=np.int64))
        if fortran_order or dtype.hasobject \
                or at + nbytes != start + info.file_size:
            return None
        return self._map[at:at + nbytes].view(dtype).reshape(shape)

    def __getitem__(self, key):
        with telemetry.startup("weights_read") as phase:
            value = self._view(self._members[key])
            _MEMBERS.inc(path="read" if value is None else "mapped")
            if value is None:
                if self._read is None:
                    self._read = np.load(self._path)
                value = self._read[key]
            elif value.ctypes.data % 64 == 0:
                value = value.copy()
            phase.moved(value.nbytes)
        return value

    def __contains__(self, key):
        return key in self._members

    def __iter__(self):
        return iter(self._members)

    def __len__(self):
        return len(self._members)


def module_shard_factory(model_name: str, model_file: Optional[str],
                         layer_start: int, layer_end: int, stage: int = 0,
                         dtype=jnp.float32,
                         params: Optional[Dict] = None,
                         unroll: Optional[bool] = None) \
        -> Tuple[Callable, Dict, ShardConfig]:
    """Build one pipeline stage: (jitted shard fn, params, shard config).

    Parity with model_cfg.py:80-95. `params` supplies a pre-restored
    parameter pytree (e.g. an Orbax stage checkpoint) and skips weight-file
    loading. Otherwise, if the weights file is missing, falls back to
    deterministic random initialization (same pytree structure) so the
    framework runs end-to-end with zero egress; a warning is logged since
    outputs then aren't pretrained.

    `unroll` selects the full-block execution layout (None = policy
    `should_unroll_blocks`); pass False where the stacked layout is
    required, e.g. params feeding the SPMD driver's stage stacking.
    """
    entry = get_model_entry(model_name)
    if model_file is None:
        model_file = entry.weights_file
    shard_config = make_shard_config(model_name, layer_start, layer_end)
    if params is not None:
        params = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, dtype=dtype
                                  if jnp.issubdtype(x.dtype, jnp.floating)
                                  else None), params)
    elif model_file and os.path.exists(model_file):
        # the family's loader reads a key and places it, key after key:
        # each read suspends the placement's phase (telemetry.startup)
        with telemetry.startup("weights_place"):
            with _TimedReads(model_file) as weights:
                params = entry.family.load_params(
                    entry.config, shard_config, weights, dtype=dtype)
    else:
        logger.warning("weights file %r not found for %s; using random init",
                       model_file, model_name)
        with telemetry.startup("weights_place"):
            params = entry.family.init_params(entry.config, shard_config,
                                              dtype=dtype)
    blocks = params.get("blocks")
    if blocks is not None and not isinstance(blocks, (tuple, list)):
        n_blocks = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        do_unroll = unroll if unroll is not None \
            else should_unroll_blocks(n_blocks)
        if do_unroll:
            with telemetry.startup("weights_place"):
                params = unstack_blocks(params)
    with telemetry.startup("programs"):
        fn = make_shard_fn(entry.family.FAMILY, entry.config, shard_config)
    logger.info("======= %s stage %d: layers [%d, %d] =======",
                model_name, stage, layer_start, layer_end)
    return fn, params, shard_config
