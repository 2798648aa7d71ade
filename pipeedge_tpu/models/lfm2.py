"""LFM2 (`model_type` lfm2_moe): most blocks mix tokens through a gated
convolution three positions wide, a few through grouped-query attention;
the leading blocks have a dense SwiGLU FFN and the rest an expert layer
routed by a sigmoid, with no shared expert.

The block, `x` [B, S, D], plain RMSNorm, no bias anywhere:
  h = x + Mixer(rms(x; operator_norm));  x' = h + FFN(rms(h; ffn_norm))
`cfg.layer_types[i]` names block i's mixer ("conv" | "full_attention": a
list, the pattern is no interval) and the first `cfg.first_k_dense` blocks
have the dense FFN. A block's kind is both, `<mixer>_<ffn>` (`block_kind`):
the FFN decides its parameter leaves, so which blocks stack into one run
(models/shard.py `BlockRuns`), and the mixer decides which cache leaf it
owns.

**Gated short convolution.** `[B | C | u] = in_proj(rms(x))`, three chunks
of D in that order; `m = B * u`; `c_t = sum_j w[:, j] * m_{t-K+1+j}`
(depthwise, causal, `K = cfg.conv_kernel`, no bias, no activation); `y =
out_proj(C * c)`. Its whole state is `m` at the last `K - 1` positions.

**Attention.** GQA; q and k are RMS-normed a head before the rotation,
which turns the whole head (halves layout); causal softmax at `Dh**-0.5`.
The window is read in its stored form (models/stage_cache.py `attend`).

**Cache: a leaf that blocks of two kinds own** (`cache_leaves`,
models/shard.py `CacheLeaf`). The attention blocks own `k`, `v` `[L_attn,
B, T, G*Dh]`, a row a position. The convolution blocks own `conv_tail`
`[L_conv, B, K - 1, D]`, a row a REQUEST, read and replaced whole by every
call; they are of two kinds (before a dense FFN, before an expert layer),
so the leaf names both and a run indexes it past the earlier runs of
either. The published cache keeps `K` positions, of which a step uses the
last `K - 1` and its own; `K - 1` are kept here, as qwen3_next's tail.

**Precision.** Weights as stored (bfloat16); activations, cache and tail
float32: products with weights through `exact_dot`, the attention's
products of two activations at `HIGHEST`. The router's top-4 of 32 is a
discrete choice that a bfloat16 computation makes differently from the
float32 reference (the three other sparse families found so on the chip:
PERF.md, PRs 27, 31, 33).

**Prefill** runs in spans of `cfg.prefill_chunk` positions through the
decode-shaped stage program: the convolution takes its tail from the cache
and leaves the span's last `K - 1` inputs there. A step is the span of one.

**The expert layer at load.** All 32 experts are held and a batch job steps
many rows at once, so a step's layer call gives every expert a group (16
tokens at 128 rows) where the other sparse families' steps give a few
experts one token: `parallel/expert.py::expert_tile` sizes the loop's tile
from that group (32 rows), not from the call's rows.

Refused by name: the forward path (`sublayer`), tp, sp and ep meshes, the
int8 cache, `--kv-pages` (a page holds positions) and speculative verify (a
rejected draft would need the tail of an earlier position).

Weight format: the published state dict (`model.layers.N.{operator_norm,
ffn_norm}.weight`, `.conv.{in_proj,conv,out_proj}.weight`, `.self_attn.
{q_proj,k_proj,v_proj,out_proj}.weight`, `.self_attn.{q_layernorm,
k_layernorm}.weight`, `.feed_forward.{w1,w2,w3}.weight` in a dense layer,
`.feed_forward.gate.weight`, `.feed_forward.expert_bias`, `.feed_forward.
experts.E.{w1,w2,w3}.weight` in an expert layer; `model.embed_tokens`,
`model.embedding_norm`, the final norm; the head is the embedding, tied).
SwiGLU is `w2(silu(w1 u) * w3 u)`.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from . import ShardConfig, decoder
from .decoder import in_row_chunks, lin
from .layers import TransformerConfig, causal_conv, rms_norm, rope_rotate
from .shard import CacheLeaf, FamilySpec
from .stage_cache import attend, cache_update_and_read

# what a block step counts into the cache's `stats` leaf, in this order
STATS = decoder.MOE_STATS + ("shortconv_positions_spanned",
                             "shortconv_positions_stepped",
                             "shortconv_tail_carries")

# activations, cache and tail (module docstring, Precision)
ACTIVATIONS = jnp.float32
# the attention's products of two activations, float32 in full: q and k are
# normed, so scores are of order 1, and the router's choice after them
# amplifies what fewer passes leave (models/qwen3_next.py `_ATTENTION`)
_ATTENTION = jax.lax.Precision.HIGHEST

_MIXERS = {"conv": "conv", "full_attention": "attn"}
_FFNS = ("dense", "experts")


def prefill_span(cfg: TransformerConfig) -> int:
    return cfg.prefill_chunk


def block_kind(cfg: TransformerConfig, block_id: int) -> str:
    """`<mixer>_<ffn>`: conv | attn, dense | experts."""
    return _MIXERS[cfg.layer_types[block_id]] + "_" \
        + _FFNS[block_id >= cfg.first_k_dense]


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """The cache's leaves (module docstring, Cache): what follows `[L, B,
    T]` in the attention blocks' `k`, `v` and `[L, B]` in the convolution
    blocks' tail, each owned by its mixer's blocks of either FFN."""
    def kinds(mixer):
        return tuple(f"{mixer}_{ffn}" for ffn in _FFNS)

    rows = CacheLeaf((cfg.kv_heads * cfg.head_dim,), ACTIVATIONS,
                     kinds("attn"))
    return {"k": rows, "v": rows,
            "conv_tail": CacheLeaf((cfg.conv_kernel - 1, cfg.hidden_size),
                                   ACTIVATIONS, kinds("conv"), whole=True),
            "stats": jax.ShapeDtypeStruct((len(STATS),), jnp.int32)}


def short_conv(p: Dict, normed, tail, cfg: TransformerConfig):
    """The gated short convolution of `normed` [B, S, D] after `tail`
    [B, K - 1, D], its inputs `m` at the positions before. -> (out [B, S,
    D], the tail after the span)."""
    gates = in_row_chunks(lambda rows: lin(p["conv_in"], rows), normed,
                          p["conv_in"].shape[0])
    before, after, u = jnp.split(gates, 3, axis=-1)         # B, C, x
    mixed, tail = causal_conv(p["conv"], before * u, tail)
    return lin(p["conv_out"], (after * mixed).astype(normed.dtype)), tail


def attention(p: Dict, normed, bcache, pos, cfg: TransformerConfig,
              prefill: bool, read_len=None):
    """GQA of `normed` [B, S, D] at [pos, pos + S) over the cached window
    below `pos` and its own rows. -> (out, the cache with the rows k, v
    recorded)."""
    b, s, _ = normed.shape
    eps, hd = cfg.layer_norm_eps, cfg.head_dim
    q_pos = jnp.asarray(pos) + jnp.arange(s)
    q = lin(p["q"]["w"], normed).reshape(b, s, cfg.num_attention_heads, hd)
    k = lin(p["k"]["w"], normed).reshape(b, s, cfg.kv_heads, hd)
    v = lin(p["v"]["w"], normed).reshape(b, s, cfg.kv_heads, hd)
    q = rope_rotate(rms_norm(p["q_norm"], q, eps), q_pos, cfg.rope_theta)
    k = rope_rotate(rms_norm(p["k_norm"], k, eps), q_pos, cfg.rope_theta)
    k, v, keep, bcache = cache_update_and_read(
        bcache, k, v, pos, prefill, s, normed.dtype, read_len=read_len)
    ctx = attend(q, k, v, keep, cfg, precision=_ATTENTION)
    return lin(p["attn_out"]["w"], ctx), bcache


def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """Cached block (the decode driver's `_block_step` contract) of any of
    the four kinds. The rows of `x` sit at [pos, pos + S). A convolution
    block takes its tail from the cache (a prefill, at `pos` 0: zeros) and
    records what it is after the span, which takes its place; an attention
    block attends the cached window below `pos` and its own rows and
    records their keys and values for `write_rows`."""
    b, s, _ = x.shape
    eps = cfg.layer_norm_eps
    normed = rms_norm(p["ln_before"], x, eps)
    counts = jnp.zeros(3, jnp.int32)
    if "conv_in" in p:
        tail = jax.lax.dynamic_index_in_dim(
            bcache.stack["conv_tail"], bcache.layer, 0, keepdims=False)
        if prefill:
            tail = jnp.zeros_like(tail)
        carried = jnp.any(tail != 0).astype(jnp.int32)
        mixed, tail = short_conv(p, normed, tail, cfg)
        bcache = bcache._replace(rows={"conv_tail": tail})
        counts = jnp.stack([jnp.int32(b * s if s > 1 else 0),
                            jnp.int32(b if s == 1 else 0), carried])
    else:
        mixed, bcache = attention(p, normed, bcache, pos, cfg, prefill,
                                  read_len)
    h = x + mixed
    delta, moe = decoder.ffn(p, rms_norm(p["ln_after"], h, eps), cfg)
    return h + delta, bcache._replace(
        rows=dict(bcache.rows, stats=jnp.concatenate([moe, counts])))


# the head is the embedding, tied, after `embedding_norm`; positions live in
# the rotation and in the convolution's tail
FAMILY = FamilySpec(name="lfm2", cached_block_step=cached_block_step,
                    **decoder.token_hooks("lfm2", ACTIVATIONS, rms_norm),
                    decoder_model=True, position_dependent_attention=True,
                    cache_leaves=cache_leaves, prefill_span=prefill_span,
                    whole_leaves=("experts",), stats_names=STATS,
                    block_kind=block_kind)


# -- loading -------------------------------------------------------------------

def _assemble(cfg: TransformerConfig, shard_config: ShardConfig, get,
              dtype) -> Dict:
    """Shard params from `get(key, shape)`, a tensor of the published
    scheme (module docstring; `decoder.loader`, `assemble_shard`). The
    router's bias stays float32, as published."""
    d, heads, groups, hd = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.kv_heads, cfg.head_dim
    first, count = cfg.held_experts or (0, cfg.n_experts)

    def scale(key, n):
        return {"scale": get(key, (n,))}

    def mlp(root, width):       # w2(silu(w1 u) * w3 u)
        return {"gate": get(root + "w1.weight", (width, d)),
                "up": get(root + "w3.weight", (width, d)),
                "down": get(root + "w2.weight", (d, width))}

    tied = []

    def table():    # embedding and head: one tensor, read or drawn once
        if not tied:
            tied.append(get("model.embed_tokens.weight", (cfg.vocab_size, d)))
        return tied[0]

    def get_embed() -> Dict:
        return {"wte": table()}

    def get_block(block_id: int, subs: tuple) -> Dict:
        decoder.whole_blocks("lfm2", subs)
        root = f"model.layers.{block_id}."
        mixer, ffn = block_kind(cfg, block_id).split("_")
        if mixer == "conv":
            p = {"conv_in": get(root + "conv.in_proj.weight", (3 * d, d)),
                 "conv": get(root + "conv.conv.weight",
                             (d, 1, cfg.conv_kernel))[:, 0].T,
                 "conv_out": get(root + "conv.out_proj.weight", (d, d))}
        else:
            att = root + "self_attn."
            p = {"q": {"w": get(att + "q_proj.weight", (heads * hd, d))},
                 "k": {"w": get(att + "k_proj.weight", (groups * hd, d))},
                 "v": {"w": get(att + "v_proj.weight", (groups * hd, d))},
                 "q_norm": scale(att + "q_layernorm.weight", hd),
                 "k_norm": scale(att + "k_layernorm.weight", hd),
                 "attn_out": {"w": get(att + "out_proj.weight",
                                       (d, heads * hd))}}
        p["ln_before"] = scale(root + "operator_norm.weight", d)
        p["ln_after"] = scale(root + "ffn_norm.weight", d)
        if ffn == "dense":
            p["mlp"] = mlp(root + "feed_forward.", cfg.intermediate_size)
            return p
        p["router"] = {
            "w": get(root + "feed_forward.gate.weight",
                     (cfg.n_experts, d)).T,
            "bias": get(root + "feed_forward.expert_bias",
                        (cfg.n_experts,))}
        held = [mlp(f"{root}feed_forward.experts.{e}.",
                    cfg.moe_intermediate_size)
                for e in range(first, first + count)]
        p["experts"] = {name: decoder.stack([one[name] for one in held])
                        for name in ("gate", "up", "down")}
        return p

    def get_final() -> Dict:
        return {"ln": scale("model.embedding_norm.weight", d),
                "head": {"w": table()}}

    return decoder.assemble_shard(
        shard_config, get_embed, get_block, get_final, dtype,
        kind=lambda block_id: block_kind(cfg, block_id),
        float32=(("router", "bias"),))


load_params, init_params = decoder.loader(_assemble)
