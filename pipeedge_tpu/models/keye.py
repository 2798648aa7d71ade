"""Keye-VL-2.0's language model: a Qwen3-MoE block with a learned sparse
attention indexer.

The block, in the family's usual four sublayers:
  sub 0: rms_norm -> GQA self-attention over a learned selection of keys
         (per-head RMSNorm of q and k, M-RoPE)    payload (ctx, residual)
  sub 1: attention output projection + residual   payload hidden
  sub 2: rms_norm -> top-k routed SwiGLU experts  payload (delta, residual)
  sub 3: delta + residual                         payload hidden
First shard: token embedding. Last shard: final RMSNorm and an untied head.
No linear has a bias.

**Selection.** Beside q, k and v the attention computes an indexer query
(`index_heads` heads of `index_head_dim`), one indexer key a position
(LayerNorm'd, kept in the cache as the leaf `ik`) and a weight a head.
A query scores every live position, `I[t, s] = sum_j w[t, j] *
relu(iq[t, j] . ik[s])` in float32, and attends the `index_topk` best
(all of them while there are no more; ties to the lower position). The
selection is a mask over the attended window, found without a sort: the
k-th largest score by bisection over its bits (`_topk_mask`).

**Cache.** Three leaves the family names (`cache_leaves`): `k` and `v`
with the KV heads folded into one axis (`[L, B, T, kv_heads * head_dim]`:
a 4 x 128 tail would be padded to a tile of 8 or 16 rows on the chip) and
`ik` `[L, B, T, index_head_dim]`, written and read by the stage cache's
`write_rows` and `read_window` like any other; and the `stats` leaf the
block's counts are added to (models/stage_cache.py).

**Precision.** Activations and the cache are float32 whatever the weights
are stored in, and every product is made to about float32 accuracy
(`exact_dot` for weights, `Precision.HIGHEST` for products of activations). In
bfloat16 the model's two discrete choices, a query's kept keys and a
token's experts, part from a float32 computation within a layer (8 roundings
of 2**-9 make 0.7% of noise in the router's input, enough to change one
of eight experts for a fifth of the tokens; from the second layer on a
twentieth of a query's 2,048 keys differ, which moves an attention output
that is a mean of 2,048 random values by a third), and the greedy tokens no
longer stay within the benchmark's tolerance of the reference's (PERF.md,
PR 27). Float32 for the router and the indexer alone does not help: what
they read is already apart.

**Prefill** runs in spans of `prefill_span(cfg)` positions through the
decode-shaped stage program, each span attending the cache written so far
and its own rows: no program holds a whole long prompt's scores, and on a
TPU none holds a chunk's either (`decoder.attend_masked`: the streaming
kernel of `ops/masked_attention.py` for a span, the einsums for a step).

The vision tower is not here: the M-RoPE takes three position rows so that
image tokens could be placed, and text gives it three equal ones.

Weight format: Qwen3-MoE's HF state dict (`model.layers.N.self_attn.
{q,k,v,o}_proj`, `q_norm`, `k_norm`, `mlp.gate`, `mlp.experts.E.{gate,up,
down}_proj`, norms, `lm_head`) and, of our naming, `self_attn.indexer.
{wq,wk,weights_proj,k_norm}`. Expert matrices stay `[out, in]` as stored
(stacked `[E, out, in]`): 600 M values a layer are not transposed on the
host.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig, decoder
from .decoder import by_head, routed_experts
from .layers import (TransformerConfig, exact_dot, layer_norm, rms_norm,
                     rope_frequencies, rope_rotate)
from .shard import FamilySpec
from .stage_cache import attend_width, read_window

# what a block step counts into the cache's `stats` leaf, in this order
STATS = decoder.MOE_STATS + ("sparse_scored", "sparse_kept") \
    + decoder.ATTEND_STATS


def prefill_span(cfg: TransformerConfig) -> int:
    """Positions a prompt is prefilled at a time: the indexer's query
    chunk. A constant of the family."""
    return cfg.index_q_chunk


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """The cache's leaves: what follows `[L, B, T]` in each, and its type.
    Float32 like the activations, whatever the weights are stored in."""
    def rows(width):
        return jax.ShapeDtypeStruct((width,), jnp.float32)
    return {"k": rows(cfg.kv_heads * cfg.head_dim),
            "v": rows(cfg.kv_heads * cfg.head_dim),
            "ik": rows(cfg.index_head_dim),
            "stats": jax.ShapeDtypeStruct((len(STATS),), jnp.int32)}


# products of two activations (scores, probabilities times values, the
# indexer's scores): float32 in full. Three bfloat16 passes (`HIGH`, about
# 16 bits) still left one greedy token in fifty 2% of the logits' range from
# the reference's (my chip runs, PR 27): a query whose 2,048th and 2,049th
# scores lie within 1e-5 keeps another key, and the layers after it amplify
_ACTIVATIONS = jax.lax.Precision.HIGHEST


def _lin(w: jax.Array, x: jax.Array) -> jax.Array:
    """x @ w, w stored [in, out]: float32 x over the weights as stored
    (`exact_dot`)."""
    return exact_dot(x, w).astype(x.dtype)


def mrope_rotate(x: jax.Array, pos3: jax.Array, theta: float,
                 section) -> jax.Array:
    """M-RoPE on [B, S, H, Dh] at positions `pos3` [3, S] (temporal,
    height, width): of the Dh/2 frequencies the first `section[0]` turn
    with the temporal position, the next `section[1]` with the height, the
    rest with the width; half-split rotation as `rope_rotate`, which this
    equals when the three rows are equal."""
    row = np.repeat(np.arange(3), section)                  # [hd/2]
    angles = pos3.astype(jnp.float32)[row].T \
        * rope_frequencies(x.shape[-1], theta)[None]        # [S, hd/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos[None, :, None] + rotated
            * sin[None, :, None]).astype(x.dtype)


def _project(p: Dict, normed: jax.Array, cfg: TransformerConfig, pos):
    """q [B,S,H,Dh], k, v [B,S,G*Dh] (the KV heads folded into one axis, as
    the cache keeps them), and the indexer's query [B,S,Hi,Di],
    key [B,S,Di] and head weights [B,S,Hi] (float32, scaled) of `normed`
    at text positions `pos` [S]."""
    b, s, _ = normed.shape
    eps = cfg.layer_norm_eps
    q = _lin(p["q"]["w"], normed).reshape(b, s, cfg.num_attention_heads, -1)
    k = _lin(p["k"]["w"], normed).reshape(b, s, cfg.kv_heads, -1)
    v = _lin(p["v"]["w"], normed)
    if cfg.qk_norm:
        q, k = rms_norm(p["q_norm"], q, eps), rms_norm(p["k_norm"], k, eps)
    pos3 = jnp.broadcast_to(pos[None], (3,) + pos.shape)
    q = mrope_rotate(q, pos3, cfg.rope_theta, cfg.mrope_section)
    k = mrope_rotate(k, pos3, cfg.rope_theta, cfg.mrope_section).reshape(
        b, s, -1)
    iq = _lin(p["index_q"]["w"], normed).reshape(b, s, cfg.index_heads, -1)
    ik = layer_norm(p["index_k_norm"], _lin(p["index_k"]["w"], normed), eps)
    iq = rope_rotate(iq, pos, cfg.rope_theta)
    ik = rope_rotate(ik[:, :, None], pos, cfg.rope_theta)[:, :, 0]
    iw = _lin(p["index_w"]["w"], normed).astype(jnp.float32) \
        * (cfg.index_heads ** -0.5 * cfg.index_head_dim ** -0.5)
    return q, k, v, iq, ik, iw


def index_scores(iq, iw, ik) -> jax.Array:
    """I [B, Q, K] float32 of indexer queries [B,Q,Hi,Di], head weights
    [B,Q,Hi] and keys [B,K,Di]."""
    dots = jnp.einsum("bqhd,bkd->bqhk", iq, ik.astype(iq.dtype),
                      preferred_element_type=jnp.float32,
                      precision=_ACTIVATIONS)
    return jnp.sum(jax.nn.relu(dots) * iw[..., None], axis=2)


def _topk_mask(score: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """The `k` largest of `score` [..., K] among `valid`, as a mask; all of
    the valid where they are `k` or fewer; ties to the lower index. No
    sort: the k-th largest is found bit by bit, 32 counts over the scores
    as unsigned keys that order as the floats do (a negative zero made
    positive first, so that equal scores have equal keys)."""
    score = jnp.where(score == 0.0, 0.0, score)
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    key = jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31))
    key = jax.lax.bitcast_convert_type(key, jnp.uint32)
    key = jnp.where(valid, key, jnp.uint32(0))      # below every float

    def narrow(i, kth):
        trial = kth | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        enough = jnp.sum(key >= trial[..., None], axis=-1) >= k
        return jnp.where(enough, trial, kth)

    kth = jax.lax.fori_loop(0, 32, narrow,
                            jnp.zeros(score.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    level = (key == kth[..., None]) & valid
    spare = k - jnp.sum(above, axis=-1, keepdims=True)

    def by_position():
        return above | (level & (jnp.cumsum(level, axis=-1) <= spare))

    # several scores at the k-th value are rare (a score is a float32 sum
    # of sixteen products), so the running count is behind a branch
    tied = jnp.any(jnp.sum(level, axis=-1, keepdims=True) > spare)
    return jax.lax.cond(tied, by_position, lambda: above | level)


def _attend_selected(q, k, v, keep):
    """Grouped-query attention of q [B,Q,H,Dh] over key parts under
    per-row masks `keep` (a [B,Q,K] a part): one softmax over all parts, a
    KV group at a time (`decoder.attend_masked`). A part's k and v are
    tuples of one [B,K,Dh] a KV head, each used as it was read: not repeated
    for its query heads, not put beside the others.
    -> ([B, Q, H*Dh], 1 where the streaming kernel ran)."""
    b, s, h, hd = q.shape
    groups = len(k[0])
    q4 = q.reshape(b, s, groups, h // groups, hd)
    out, fused = zip(*(decoder.attend_masked(
        q4[:, :, g], [kp[g] for kp in k], [vp[g] for vp in v], keep)
        for g in range(groups)))
    return jnp.stack(out, axis=2).astype(q.dtype).reshape(b, s, h * hd), \
        fused[0]


def sparse_attention(q, iq, iw, q_pos, parts, cfg: TransformerConfig):
    """Attention of the queries at absolute positions `q_pos` [Q] over the
    key `parts`: each (k, v: a [B,K,Dh] a KV head, ik [B,K,Di], k_pos [K],
    live [K] or None). A key may be attended if it is live and not after
    the query; of those the indexer keeps `cfg.index_topk`. Queries run in
    chunks so that no chunk's scores pass `decoder.SCORE_BYTES`.

    Returns (ctx [B, Q, H*Dh], scored, kept, fused): the counts of positions
    the indexer scored and of positions attended, and 1 where the attention
    took the streaming kernel, int32."""
    b, n_q, h, _ = q.shape
    n_keys = sum(part[2].shape[1] for part in parts)

    def one_chunk(queries, positions):
        (q_c, iq_c, iw_c), (pos_c,) = queries, positions
        valid = []
        for _, _, _, k_pos, live in parts:
            ok = k_pos[None, :] <= pos_c[:, None]
            valid.append(ok if live is None else ok & live[None, :])
        if n_keys <= cfg.index_topk:        # nothing to select
            keep = [jnp.broadcast_to(ok[None], (b,) + ok.shape)
                    for ok in valid]
        else:
            score = jnp.concatenate(
                [index_scores(iq_c, iw_c, part[2]) for part in parts], -1)
            mask = _topk_mask(score, jnp.concatenate(valid, -1)[None],
                              cfg.index_topk)
            keep = jnp.split(mask, np.cumsum(
                [part[2].shape[1] for part in parts])[:-1], axis=-1)
        ctx, fused = _attend_selected(q_c, [part[0] for part in parts],
                                      [part[1] for part in parts], keep)
        scored = b * sum(jnp.sum(ok, dtype=jnp.int32) for ok in valid)
        kept = sum(jnp.sum(m, dtype=jnp.int32) for m in keep)
        return ctx, scored, kept, jnp.int32(fused)

    # scores are live one KV group at a time
    ctx, scored, kept, fused = decoder.map_query_chunks(
        one_chunk, decoder.query_chunk(
            n_q, b * (h // cfg.kv_heads) * n_keys * 4),
        (q, iq, iw), (q_pos,))
    return ctx, scored, kept, jnp.minimum(fused, 1)


def sublayer(p: Dict, sub: int, data, cfg: TransformerConfig,
             attention_fn=None):
    """One of the 4 schedulable sublayers, over a whole sequence."""
    if attention_fn is not None:
        raise NotImplementedError(
            "keye attention reads absolute positions and a learned "
            "selection; the sequence-parallel attention override is not "
            "supported")
    if sub == 0:
        normed = rms_norm(p["ln_before"], data, cfg.layer_norm_eps)
        pos = jnp.arange(normed.shape[1])
        q, k, v, iq, ik, iw = _project(p, normed, cfg, pos)
        ctx, *_ = sparse_attention(
            q, iq, iw, pos, [(by_head(k, cfg.kv_heads),
                              by_head(v, cfg.kv_heads), ik, pos, None)], cfg)
        return (ctx, data)
    if sub == 1:
        ctx, skip = data
        return _lin(p["attn_out"]["w"], ctx) + skip
    if sub == 2:
        normed = rms_norm(p["ln_after"], data, cfg.layer_norm_eps)
        return (routed_experts(p, normed, cfg)[0], data)
    if sub == 3:
        delta, skip = data
        return delta + skip
    raise ValueError(f"sublayer must be 0..3, got {sub}")


def finalize(p: Dict, hidden: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Final RMSNorm + LM head (stored `[in, out]`) -> [B, S, vocab] logits."""
    return _lin(p["head"]["w"], rms_norm(p["ln"], hidden,
                                         cfg.layer_norm_eps))


def span_embed(pe: Dict, tok: jax.Array, pos) -> jax.Array:
    """Token embedding alone [B, K] -> [B, K, D] (positions live in the
    rotation), float32 from here on (module docstring, Precision)."""
    return jnp.take(pe["wte"], tok, axis=0).astype(jnp.float32)


def embed(p: Dict, input_ids: jax.Array, cfg: TransformerConfig) -> jax.Array:
    return span_embed(p, input_ids, 0)


def decode_embed(pe: Dict, tok: jax.Array, pos) -> jax.Array:
    """Single decode-step token embed [B, 1, D]: the rows gathered `[B]`,
    the axis added after (the kit's gathers `[B, 1]`: another program)."""
    return jnp.take(pe["wte"], tok.reshape(-1), axis=0)[:, None].astype(
        jnp.float32)


def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """KV-cached block (the decode driver's `_block_step` contract): the
    rows of `x` sit at [pos, pos + S), attend the cached window [0, width)
    below `pos` and themselves, and are recorded for `write_rows` with
    their indexer keys and the step's counts. A prefill (`pos` 0, nothing
    cached) attends its own rows alone."""
    b, s, _ = x.shape
    normed = rms_norm(p["ln_before"], x, cfg.layer_norm_eps)
    q_pos = jnp.asarray(pos) + jnp.arange(s)
    q, k, v, iq, ik, iw = _project(p, normed, cfg, q_pos)
    stack = bcache.stack
    # through the cache's dtype, as if read back from it
    k, v, ik = (new.astype(stack[name].dtype).astype(x.dtype)
                for name, new in (("k", k), ("v", v), ("ik", ik)))
    parts = [(by_head(k, cfg.kv_heads), by_head(v, cfg.kv_heads), ik,
              q_pos, None)]
    if not prefill:
        width = attend_width(bcache, read_len)
        lanes = [slice(g * cfg.head_dim, (g + 1) * cfg.head_dim)
                 for g in range(cfg.kv_heads)]
        at = jnp.arange(width)
        parts.insert(0, tuple(
            tuple(read_window(stack[name], bcache.layer, width, head)
                  for head in lanes) for name in ("k", "v"))
            + (read_window(stack["ik"], bcache.layer, width), at,
               at < pos))
    ctx, scored, kept, fused = sparse_attention(q, iq, iw, q_pos, parts,
                                                cfg)
    h = _lin(p["attn_out"]["w"], ctx) + x
    delta, moe = routed_experts(
        p, rms_norm(p["ln_after"], h, cfg.layer_norm_eps), cfg)
    stats = jnp.concatenate([moe.astype(jnp.int32),
                             jnp.stack([jnp.int32(1), scored, kept, fused])])
    rows = {"k": k, "v": v, "ik": ik, "stats": stats}
    return h + delta, bcache._replace(rows=rows)


# the hooks are keye's own, not `decoder.token_hooks`: of the five sparse
# families it alone runs the forward path (`sublayer`), stores its matrices
# `[in, out]` (`_lin` in `finalize`) and gathers a step's rows `[B]`
FAMILY = FamilySpec(name="keye", embed=embed, sublayer=sublayer,
                    finalize=finalize, cached_block_step=cached_block_step,
                    decode_embed=decode_embed, span_embed=span_embed,
                    decoder_model=True, position_dependent_attention=True,
                    cache_leaves=cache_leaves, prefill_span=prefill_span,
                    whole_leaves=("experts",), stats_names=STATS)


def _assemble(cfg: TransformerConfig, shard_config: ShardConfig, get,
              dtype) -> Dict:
    """Shard params from `get(key, shape)`, a tensor of Qwen3-MoE's HF
    state dict and our indexer's (module docstring; `decoder.loader`,
    `assemble_shard`). A linear's kernel `[out, in]` is turned `[in, out]`
    on the host; the experts' stay as stored. A block cut by the partition
    (the forward path's) gets the leaves of its sublayers `subs`."""
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    hd, hi = cfg.head_dim, cfg.index_head_dim
    qd, kvd = cfg.num_attention_heads * hd, cfg.kv_heads * hd

    def scale(key, n):
        return {"scale": get(key, (n,))}

    def turned(key, n_out, n_in):
        return {"w": get(key, (n_out, n_in)).T}

    def get_embed() -> Dict:
        return {"wte": get("model.embed_tokens.weight", (cfg.vocab_size, d))}

    def get_block(block_id: int, subs: tuple) -> Dict:
        root = f"model.layers.{block_id}."
        att, idx = root + "self_attn.", root + "self_attn.indexer."
        p: Dict = {}
        if 0 in subs:
            p["ln_before"] = scale(root + "input_layernorm.weight", d)
            for name, width in (("q", qd), ("k", kvd), ("v", kvd)):
                p[name] = turned(att + name + "_proj.weight", width, d)
                if cfg.qk_norm and name != "v":
                    p[name + "_norm"] = scale(att + name + "_norm.weight", hd)
            p["index_q"] = turned(idx + "wq.weight", cfg.index_heads * hi, d)
            p["index_k"] = turned(idx + "wk.weight", hi, d)
            p["index_w"] = turned(idx + "weights_proj.weight",
                                  cfg.index_heads, d)
            p["index_k_norm"] = {"scale": get(idx + "k_norm.weight", (hi,)),
                                 "bias": get(idx + "k_norm.bias", (hi,))}
        if 1 in subs:
            p["attn_out"] = turned(att + "o_proj.weight", d, qd)
        if 2 in subs:
            p["ln_after"] = scale(root + "post_attention_layernorm.weight", d)
            p["router"] = turned(root + "mlp.gate.weight", cfg.n_experts, d)
            p["experts"] = {
                name: decoder.stack([
                    get(f"{root}mlp.experts.{e}.{name}_proj.weight", shape)
                    for e in range(cfg.n_experts)])
                for name, shape in (("gate", (f, d)), ("up", (f, d)),
                                    ("down", (d, f)))}
        return p

    def get_final() -> Dict:
        return {"ln": scale("model.norm.weight", d),
                "head": turned("lm_head.weight", cfg.vocab_size, d)}

    return decoder.assemble_shard(shard_config, get_embed, get_block,
                                  get_final, dtype)


def _undrawn(key: str, shape: tuple):
    """What `init_params` does not draw: norms of scale 1 and bias 0."""
    if key.endswith("norm.bias"):
        return np.zeros(shape, np.float32)
    return decoder.norm_ones(key, shape)


load_params, init_params = decoder.loader(_assemble, _undrawn)
