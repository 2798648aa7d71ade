"""Kimi-K2 (`model_type` kimi_k2): DeepSeek-V3's block. Latent attention
(MLA) over a cache of one shared row a position, a leading dense layer, then
expert layers routed by a sigmoid beside a shared expert.

The block, `x` [B, S, D], RMSNorm everywhere, no bias anywhere:
  h = x + MLA(rms(x));  x' = h + FFN(rms(h))
FFN is a SwiGLU of `intermediate_size` in the first `first_k_dense` blocks
and the expert layer after them (parallel/expert.py `topk_ffn_delta`:
sigmoid scores, the choice made on score + bias, gates normalised and
scaled, the chip's `held_experts` of `n_experts`, one shared expert). The
two kinds of block have different leaves, so a stage holds them as runs
(`block_kind`, models/shard.py `BlockRuns`), each its own scan over the one
cache stack.

**MLA.** `c_q = rms(W_qa u)`; `q = W_qb c_q` in heads of (nope | rope).
`[c_kv | k_pe] = W_kva u`; `c_kv = rms(c_kv)`; `k_pe` is one rotary key for
all heads. `[k_nope | v] = W_kvb c_kv` by head. Score of head j = (q_nope_j
. k_nope_j + q_pe_j . k_pe) * s, `s = (nope + rope)**-0.5 * m**2`, `m = 0.1
* mscale_all_dim * ln(factor) + 1` (YaRN). The rotation keeps the
checkpoint's layout: pairs interleaved, de-interleaved before the halves
are rotated, for q_pe and k_pe alike.

**Cache.** Two leaves a position a layer, shared by all heads: `c_kv`
[L, B, T, kv_lora_rank] after its norm and `k_pe` [L, B, T, rope] after its
rotation (576 values where the heads' keys and values would be 20,480), and
`stats`. Compiled for a described v5e at the cell's size (64 rows, 4,096
positions, 5 layers, float32) the chip keeps `c_kv` at its own size (2.68
GB; 512 is four lane tiles) and `k_pe` too (0.34 GB: with 64 in the minor
axis it tiles the positions by 128 beside it and pads nothing), which one
576-wide leaf would not (4.5 tiles, padded to 5).

**Two attention paths, one result.** The rows of this call (a prompt's
span) attend each other in the expanded form: each latent row is expanded
to its heads' k_nope and v once, in the call that writes it. Rows read from
the cache (every decode step; a span's view of earlier spans) are attended
in the absorbed form: with `W_kvb` split by head into `W_uk_j`, `W_uv_j`,
`q'_j = W_uk_j^T q_nope_j`, score = (q'_j . c_kv + q_pe_j . k_pe) * s, `o_j
= W_uv_j (sum p c_kv)`: 64 heads over one 576-wide row, and no cached row is
ever expanded again. One softmax runs over both. A decode step's own row is
attended in the absorbed form as well. Queries run in chunks so that no
chunk's scores pass `decoder.SCORE_BYTES`.

**Precision.** Weights as stored (bfloat16). Activations and the cache are
float32: products with weights through `exact_dot` / `exact_einsum`,
products of two activations at `Precision.HIGH` (three bfloat16 passes; the
attention makes no discrete choice, so 16 bits suffice where keye's
selection needed 24). The router's top-8 of 384 is a discrete choice that a
bfloat16 computation makes differently from the float32 reference, and the
benchmark's comparison then fails (PERF.md, PR 31).

**Prefill** runs in spans of `cfg.prefill_chunk` positions through the
decode-shaped stage program, as keye's does.

Refused by name: the forward path (`sublayer`: runs of blocks are not in
`shard_apply`), tp, sp and ep meshes, the int8 cache, `--kv-pages`.

Weight format: DeepSeek-V3's HF state dict (`model.layers.N.self_attn.
{q_a_proj, q_a_layernorm, q_b_proj, kv_a_proj_with_mqa, kv_a_layernorm,
kv_b_proj, o_proj}`, `mlp.{gate,up,down}_proj` in a dense layer, `mlp.gate.
{weight, e_score_correction_bias}`, `mlp.experts.E.*`, `mlp.shared_experts.
*` in an expert layer). Every matrix stays `[out, in]` as stored; of a
layer's experts the held ones are read, by their published index.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig, decoder, layers
from .decoder import in_row_chunks, lin
from .layers import TransformerConfig, exact_einsum, rms_norm, rope_frequencies
from .shard import FamilySpec
from .stage_cache import attend_width, read_window

# what a block step counts into the cache's `stats` leaf, in this order
STATS = decoder.MOE_STATS + ("mla_rows_written", "mla_rows_expanded",
                             "mla_rows_read")

# activations and cache (module docstring, Precision)
ACTIVATIONS = jnp.float32


def prefill_span(cfg: TransformerConfig) -> int:
    return cfg.prefill_chunk


def block_kind(cfg: TransformerConfig, block_id: int) -> str:
    return "dense" if block_id < cfg.first_k_dense else "experts"


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """What follows `[L, B, T]` in each leaf of the cache."""
    return {"c_kv": jax.ShapeDtypeStruct((cfg.kv_lora_rank,), ACTIVATIONS),
            "k_pe": jax.ShapeDtypeStruct((cfg.qk_rope_head_dim,),
                                         ACTIVATIONS),
            "stats": jax.ShapeDtypeStruct((len(STATS),), jnp.int32)}


def yarn_frequencies(cfg: TransformerConfig) -> np.ndarray:
    """The rotation's `qk_rope_head_dim / 2` frequencies, under YaRN where
    the model says (`layers.yarn_frequencies`)."""
    if not cfg.rope_yarn:
        return rope_frequencies(cfg.qk_rope_head_dim, cfg.rope_theta)
    return layers.yarn_frequencies(cfg.qk_rope_head_dim, cfg.rope_theta,
                                   *cfg.rope_yarn[:4])


def attention_scale(cfg: TransformerConfig) -> float:
    """s of the module docstring. The cosine and sine carry mscale /
    mscale_all_dim, which is 1 for this model and is not applied."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_yarn:
        factor, all_dim = cfg.rope_yarn[0], cfg.rope_yarn[5]
        if factor > 1:
            scale *= (0.1 * all_dim * math.log(factor) + 1.0) ** 2
    return scale


def rotate(x: jax.Array, pos: jax.Array, cfg: TransformerConfig):
    """x [B, S, ..., R] turned at positions `pos` [S]: pairs (0,1), (2,3),
    ... de-interleaved into halves, then the half-split rotation."""
    angles = pos.astype(jnp.float32)[:, None] * yarn_frequencies(cfg)[None]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)      # [S, R]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    xf = x.astype(jnp.float32)
    xf = jnp.concatenate([xf[..., 0::2], xf[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return (xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(
        x.dtype)


def _queries(p: Dict, normed, pos, cfg: TransformerConfig):
    """(q_nope [B,S,H,Dn], q_pe [B,S,H,Dr] rotated) of `normed`."""
    b, s, _ = normed.shape
    heads = cfg.num_attention_heads
    eps = cfg.layer_norm_eps
    q = in_row_chunks(
        lambda rows: lin(p["q_b"]["w"], rms_norm(
            p["q_a_norm"], lin(p["q_a"]["w"], rows), eps)),
        normed, heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    q = q.reshape(b, s, heads, -1)
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    return q_nope, rotate(q_pe, pos, cfg)


def _latent(p: Dict, normed, pos, cfg: TransformerConfig):
    """(c_kv [B,S,C] normed, k_pe [B,S,Dr] rotated) of `normed`: the cache's
    row."""
    c_kv, k_pe = jnp.split(lin(p["kv_a"]["w"], normed),
                           [cfg.kv_lora_rank], axis=-1)
    return (rms_norm(p["kv_a_norm"], c_kv, cfg.layer_norm_eps),
            rotate(k_pe, pos, cfg))


def expand(p: Dict, c_kv: jax.Array):
    """(k_nope [B,K,H,Dn], v [B,K,H,Dv]) of latent rows c_kv [B,K,C]."""
    return (exact_einsum("bkc,hdc->bkhd", c_kv, p["w_uk"]).astype(
        c_kv.dtype), exact_einsum("bkc,hdc->bkhd", c_kv, p["w_uv"]).astype(
            c_kv.dtype))


def _precision(x: jax.Array):
    """Of a product of two activations (module docstring, Precision)."""
    return jax.lax.Precision.HIGH if x.dtype == jnp.float32 else None


def _attend_chunk(p: Dict, q_nope, q_pe, latent, own, scale: float):
    """Context [B, Q, H, Dv] of one chunk of queries. `latent`: parts
    attended in the absorbed form, each (c_kv [B,K,C], k_pe [B,K,Dr], keep
    [Q,K]); `own`: None or the expanded part (k_nope [B,K,H,Dn], v
    [B,K,H,Dv], k_pe [B,K,Dr], keep [Q,K]). One softmax over all keys."""
    dtype, precision = q_nope.dtype, _precision(q_nope)

    def dots(spec, a, b):
        return jnp.einsum(spec, a, b.astype(dtype), precision=precision,
                          preferred_element_type=jnp.float32)

    scores = []
    if latent:
        q_lat = exact_einsum("bqhd,hdc->bqhc", q_nope, p["w_uk"]).astype(
            dtype)
    for c_kv, k_pe, keep in latent:
        part = dots("bqhc,bkc->bhqk", q_lat, c_kv) \
            + dots("bqhr,bkr->bhqk", q_pe, k_pe)
        scores.append(jnp.where(keep[None, None], part * scale, -1e30))
    if own is not None:
        k_nope, v, k_pe, keep = own
        part = dots("bqhd,bkhd->bhqk", q_nope, k_nope) \
            + dots("bqhr,bkr->bhqk", q_pe, k_pe)
        scores.append(jnp.where(keep[None, None], part * scale, -1e30))
    top = jnp.max(jnp.concatenate(
        [jnp.max(sc, axis=-1, keepdims=True) for sc in scores], -1),
        axis=-1, keepdims=True)
    probs = [jnp.exp(sc - top) for sc in scores]
    total = sum(jnp.sum(pr, axis=-1, keepdims=True) for pr in probs)
    probs = [(pr / total).astype(dtype) for pr in probs]
    out = 0.0
    if latent:
        mixed = sum(dots("bhqk,bkc->bqhc", pr, part[0])
                    for pr, part in zip(probs, latent))
        out = exact_einsum("bqhc,hdc->bqhd", mixed.astype(dtype), p["w_uv"])
    if own is not None:
        out = out + dots("bhqk,bkhd->bqhd", probs[-1], own[1])
    return out.astype(dtype)


def latent_attention(p: Dict, q_nope, q_pe, latent, own,
                     cfg: TransformerConfig) -> jax.Array:
    """-> [B, Q, H * Dv]; arguments as `_attend_chunk`'s, all queries."""
    b, n_q, heads, _ = q_nope.shape
    n_keys = sum(part[0].shape[1] for part in latent) \
        + (own[0].shape[1] if own is not None else 0)
    scale = attention_scale(cfg)

    def one(queries, keeps):
        parts = [part[:2] + (keep,) for part, keep in zip(latent, keeps)]
        mine = None if own is None else own[:3] + (keeps[-1],)
        return _attend_chunk(p, *queries, parts, mine, scale)

    ctx = decoder.map_query_chunks(
        one, decoder.query_chunk(n_q, b * heads * n_keys * 4),
        (q_nope, q_pe),
        tuple(part[-1] for part in list(latent) + ([own] if own is not None
                                                   else [])))
    return ctx.reshape(b, n_q, -1)


def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """Cached block (the decode driver's `_block_step` contract): the rows
    of `x` sit at [pos, pos + S), attend the cached window below `pos` in
    the absorbed form and themselves in the expanded form (a single row:
    absorbed too), and are recorded for `write_rows` as latent rows with
    the step's counts. A prefill (`pos` 0) reads no cache."""
    b, s, _ = x.shape
    normed = rms_norm(p["ln_before"], x, cfg.layer_norm_eps)
    q_pos = jnp.asarray(pos) + jnp.arange(s)
    q_nope, q_pe = _queries(p, normed, q_pos, cfg)
    c_kv, k_pe = _latent(p, normed, q_pos, cfg)
    stack = bcache.stack
    # through the cache's dtype, as if read back from it
    c_kv = c_kv.astype(stack["c_kv"].dtype).astype(x.dtype)
    k_pe = k_pe.astype(stack["k_pe"].dtype).astype(x.dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    latent, own, read = [], None, jnp.int32(0)
    if s > 1:
        own = expand(p, c_kv) + (k_pe, causal)
    else:
        latent.append((c_kv, k_pe, causal))
    if not prefill:
        width = attend_width(bcache, read_len)
        live = jnp.broadcast_to(jnp.arange(width) < pos, (s, width))
        latent.insert(0, (read_window(stack["c_kv"], bcache.layer, width),
                          read_window(stack["k_pe"], bcache.layer, width),
                          live))
        read = (b * jnp.asarray(pos)).astype(jnp.int32)
    ctx = latent_attention(p, q_nope, q_pe, latent, own, cfg)
    h = lin(p["attn_out"]["w"], ctx) + x
    delta, moe = decoder.ffn(p, rms_norm(p["ln_after"], h, cfg.layer_norm_eps),
                             cfg)
    stats = jnp.concatenate([moe, jnp.stack(
        [jnp.int32(b * s), jnp.int32(b * s if s > 1 else 0), read])])
    return h + delta, bcache._replace(
        rows={"c_kv": c_kv, "k_pe": k_pe, "stats": stats})


FAMILY = FamilySpec(name="kimi", cached_block_step=cached_block_step,
                    **decoder.token_hooks("kimi", ACTIVATIONS, rms_norm),
                    decoder_model=True, position_dependent_attention=True,
                    cache_leaves=cache_leaves, prefill_span=prefill_span,
                    whole_leaves=("experts",), stats_names=STATS,
                    block_kind=block_kind)


def _assemble(cfg: TransformerConfig, shard_config: ShardConfig, get,
              dtype) -> Dict:
    """Shard params from `get(key, shape)`, a tensor of DeepSeek-V3's HF
    state dict (module docstring; `decoder.loader`, `assemble_shard`). The
    router's correction bias stays float32, as published."""
    d, heads = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, v_dim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    rank, f = cfg.kv_lora_rank, cfg.moe_intermediate_size
    first, count = cfg.held_experts or (0, cfg.n_experts)

    def scale(key, n):
        return {"scale": get(key, (n,))}

    def mlp(root, width):
        return {"gate": get(root + "gate_proj.weight", (width, d)),
                "up": get(root + "up_proj.weight", (width, d)),
                "down": get(root + "down_proj.weight", (d, width))}

    def get_embed() -> Dict:
        return {"wte": get("model.embed_tokens.weight",
                           (cfg.vocab_size, d))}

    def get_block(block_id: int, subs: tuple) -> Dict:
        decoder.whole_blocks("kimi", subs)
        root = f"model.layers.{block_id}."
        att = root + "self_attn."
        kv_b = get(att + "kv_b_proj.weight", (heads * (nope + v_dim), rank))
        kv_b = kv_b.reshape(heads, nope + v_dim, rank)
        p = {"ln_before": scale(root + "input_layernorm.weight", d),
             "q_a": {"w": get(att + "q_a_proj.weight",
                              (cfg.q_lora_rank, d))},
             "q_a_norm": scale(att + "q_a_layernorm.weight",
                               cfg.q_lora_rank),
             "q_b": {"w": get(att + "q_b_proj.weight",
                              (heads * (nope + rope), cfg.q_lora_rank))},
             "kv_a": {"w": get(att + "kv_a_proj_with_mqa.weight",
                               (rank + rope, d))},
             "kv_a_norm": scale(att + "kv_a_layernorm.weight", rank),
             "w_uk": kv_b[:, :nope], "w_uv": kv_b[:, nope:],
             "attn_out": {"w": get(att + "o_proj.weight",
                                   (d, heads * v_dim))},
             "ln_after": scale(root + "post_attention_layernorm.weight", d)}
        if block_kind(cfg, block_id) == "dense":
            p["mlp"] = mlp(root + "mlp.", cfg.intermediate_size)
            return p
        p["router"] = {
            "w": get(root + "mlp.gate.weight", (cfg.n_experts, d)).T,
            "bias": get(root + "mlp.gate.e_score_correction_bias",
                        (cfg.n_experts,))}
        held = [mlp(f"{root}mlp.experts.{e}.", f)
                for e in range(first, first + count)]
        p["experts"] = {name: decoder.stack([one[name] for one in held])
                        for name in ("gate", "up", "down")}
        p["shared"] = mlp(root + "mlp.shared_experts.",
                          f * cfg.n_shared_experts)
        return p

    def get_final() -> Dict:
        return {"ln": scale("model.norm.weight", d),
                "head": {"w": get("lm_head.weight", (cfg.vocab_size, d))}}

    return decoder.assemble_shard(
        shard_config, get_embed, get_block, get_final, dtype,
        kind=lambda block_id: block_kind(cfg, block_id),
        float32=(("router", "bias"),))


load_params, init_params = decoder.loader(_assemble)
