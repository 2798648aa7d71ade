"""Laguna (`model_type` laguna): grouped-query attention layers of two kinds,
a few that attend every position and most that attend the last
`sliding_window`, with different numbers of query heads and different
rotations; a leading dense SwiGLU FFN, then many small experts routed by a
softmax beside a gated shared one.

The window-and-full block of two families. What differs between them is
read off the configuration and off a block's leaves, never off the family's
name: the gate a head (`cfg.head_gate`: a block has a `gate` leaf or none),
the shared expert (`cfg.n_shared_experts`: `shared`, `shared_gate`), the
leading dense layers (`cfg.first_k_dense`), the routed scaling factor, the
heads and the rotation of each kind of layer. `models/mellum.py` is the
second family (no gate, no shared expert, no dense layer, one head count,
the whole head turned in both kinds) and holds no code of the block.

The block, `x` [B, S, D], plain RMSNorm, no bias anywhere:
  h = x + Attn(rms(x; input_layernorm));
  x' = h + FFN(rms(h; post_attention_layernorm))
`cfg.layer_types[i]` names block i's attention ("full_attention" |
"sliding_attention"), `cfg.layer_heads[i]` its query heads, and the first
`cfg.first_k_dense` blocks have the dense FFN. A block's kind is both,
`<mixer>_<ffn>` (`block_kind`): the attention decides the shapes of `q`,
`gate` and `attn_out` and which cache leaves the block owns, the FFN its
other leaves, so which blocks stack into one run (models/shard.py
`BlockRuns`).

**Attention**, both kinds, `H` the block's own query heads (read off its
`q` leaf, and held to the configuration's count for its kind): `q` (D -> H x
Dh), `k`, `v` (D -> G x Dh); q and k RMS-normed a head; rotated; causal
softmax at `Dh**-0.5`, `H / G` query heads a KV head; each head's output
times `sigmoid(g_proj u)`, one gate a head, before `o_proj`.
- full: every position below and the query's own. The first
  `partial_rotary_factor` of a head's lanes turn, by YaRN's frequencies
  (`layers.yarn_frequencies`), cosine and sine times the attention factor.
- sliding: query `q` attends `q - W < p <= q`. The whole head turns, plain,
  base `cfg.sliding_rope_theta`.

**Cache: two lengths in one stage** (`cache_leaves`, models/shard.py
`CacheLeaf`). The full blocks own `k`, `v` `[L_full, B, T, G*Dh]`, a row a
position up to the stage's `max_len`, read as the ladder's window
(`attend_bucket`). The sliding blocks own `k_ring`, `v_ring` `[L_sliding, B,
W, G*Dh]`, a RING of the last `W` positions (models/stage_cache.py, "A ring"):
written at `pos mod W`, read whole at every position, masked by what each
slot holds. At 32 rows x 8,192 positions in float32 the three window layers
of the cell's cut keep 0.40 GB where rows a position would be 6.4 GB.

**Precision.** Weights as stored (bfloat16); activations and cache float32:
products with weights through `exact_dot`, the attention's products of two
activations at `HIGHEST` (q and k are normed, so scores are of order 1, and
the router's top-8 of 256 after them is a discrete choice that a narrower
computation makes differently from the float32 reference: PERF.md, PRs 27,
31, 33).

**Prefill** runs in spans of `cfg.prefill_chunk` positions through the
decode-shaped stage program: a span's window layers attend their ring and
the span's own rows, not the ladder's width. A step is the span of one.

**Served**, a request's rows step with every other running row
(`rows_block_step`, parallel/decode_rows.py): a slot of the stage-wide cache
is rows to `max_len` in the full blocks' leaves and a ring in the sliding
blocks', each ring at its own row's position; the prompt pass runs in the
spans above on the request's own cache.

Refused by name: the forward path (`sublayer`), tp, sp and ep meshes, the
int8 cache, `--kv-pages` (a page holds positions of one length) and
speculative verify (a rejected draft's rows have overwritten slots of the
ring).

Weight format: Qwen3-MoE's state dict with a gate (`model.layers.N.
{input_layernorm,post_attention_layernorm}.weight`, `.self_attn.{q_proj,
k_proj,v_proj,o_proj,g_proj}.weight`, `.self_attn.{q_norm,k_norm}.weight`,
`.mlp.{gate_proj,up_proj,down_proj}.weight` in the dense layer, `.mlp.gate.
weight`, `.mlp.experts.E.*`, `.mlp.shared_expert.*`, `.mlp.
shared_expert_gate.weight` in an expert layer; `model.embed_tokens`,
`model.norm`, `lm_head`, untied).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig, decoder
from .decoder import in_row_chunks, lin
from .layers import (TransformerConfig, rms_norm, rotate_halves,
                     rope_frequencies, yarn_frequencies)
from .shard import CacheLeaf, FamilySpec
from .stage_cache import (RowsAt, Window, attend_rows, cache_update_and_read,
                          read_window, rows_walked)

# what a block step counts into the cache's `stats` leaf, in this order
STATS = decoder.MOE_STATS + ("swa_positions_read", "swa_positions_live",
                             "swa_ring_wraps")

# activations and cache (module docstring, Precision)
ACTIVATIONS = jnp.float32
_ATTENTION = jax.lax.Precision.HIGHEST

_MIXERS = {"full_attention": "full", "sliding_attention": "sliding"}
_FFNS = ("dense", "routed")


def prefill_span(cfg: TransformerConfig) -> int:
    return cfg.prefill_chunk


def block_kind(cfg: TransformerConfig, block_id: int) -> str:
    """`<mixer>_<ffn>`: full | sliding, dense | routed."""
    return _MIXERS[cfg.layer_types[block_id]] + "_" \
        + _FFNS[block_id >= cfg.first_k_dense]


def heads_of(cfg: TransformerConfig, mixer: str) -> int:
    """The query heads of the blocks whose attention is `mixer` ("full" |
    "sliding"): one count a kind."""
    counts = {heads for kind, heads in zip(cfg.layer_types, cfg.layer_heads)
              if _MIXERS[kind] == mixer}
    if len(counts) != 1:
        raise ValueError(f"the {mixer} layers have query heads {counts}: "
                         "one count a kind of layer")
    return counts.pop()


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """The cache's leaves (module docstring, Cache): what follows `[L, B,
    T]` in the full blocks' `k`, `v` and `[L, B, W]` in the sliding blocks'
    rings, each owned by its mixer's blocks of either FFN."""
    def rows(mixer, length=0):
        return CacheLeaf((cfg.kv_heads * cfg.head_dim,), ACTIVATIONS,
                         tuple(f"{mixer}_{ffn}" for ffn in _FFNS),
                         length=length)

    return {"k": rows("full"), "v": rows("full"),
            "k_ring": rows("sliding", cfg.sliding_window),
            "v_ring": rows("sliding", cfg.sliding_window),
            "stats": jax.ShapeDtypeStruct((len(STATS),), jnp.int32)}


def full_frequencies(cfg: TransformerConfig) -> np.ndarray:
    """The full layers' frequencies: YaRN over the lanes that turn."""
    turned = int(cfg.head_dim * cfg.partial_rotary_factor)
    if not cfg.rope_yarn:
        return rope_frequencies(turned, cfg.rope_theta)
    return yarn_frequencies(turned, cfg.rope_theta, *cfg.rope_yarn[:4])


def rotate(x: jax.Array, pos: jax.Array, cfg: TransformerConfig,
           sliding: bool) -> jax.Array:
    """x [B, S, H, Dh] at positions `pos` [S], by its layer's scheme (module
    docstring): halves layout in both."""
    if sliding:
        return rotate_halves(x, pos, rope_frequencies(
            x.shape[-1], cfg.sliding_rope_theta))
    freqs = full_frequencies(cfg)
    head, rest = jnp.split(x, [2 * len(freqs)], axis=-1)
    scale = cfg.rope_yarn[4] if cfg.rope_yarn else 1.0
    return jnp.concatenate([rotate_halves(head, pos, freqs, scale), rest],
                           axis=-1)


def _attend_group(q, ks, vs, keeps):
    """Context [B, Q, r, Dh] of ONE KV group's queries q [B, Q, r, Dh] over
    key parts: `ks` and `vs` that group's [B, K, Dh] a part, `keeps` a [Q, K]
    a part. One softmax over all parts."""
    hd = q.shape[-1]
    scores = [jnp.where(keep[None, None], jnp.einsum(
        "bqrd,bkd->brqk", q, k.astype(q.dtype),
        preferred_element_type=jnp.float32, precision=_ATTENTION)
        * hd ** -0.5, -1e30) for k, keep in zip(ks, keeps)]
    top = jnp.max(jnp.concatenate(
        [jnp.max(sc, axis=-1, keepdims=True) for sc in scores], -1),
        axis=-1, keepdims=True)
    # the weights are divided by their sum after they have met the values:
    # one pass over [.., Q, Dh] and not one over [.., Q, K]
    probs = [jnp.exp(sc - top) for sc in scores]
    total = sum(jnp.sum(pr, axis=-1) for pr in probs)           # [B, r, Q]
    mixed = sum(jnp.einsum(
        "brqk,bkd->bqrd", pr.astype(q.dtype), v.astype(q.dtype),
        preferred_element_type=jnp.float32, precision=_ATTENTION)
        for pr, v in zip(probs, vs))
    return (mixed / jnp.moveaxis(total, 1, 2)[..., None]).astype(q.dtype)


def attend(q, ks, vs, keeps) -> jax.Array:
    """Grouped-query attention of q [B, Q, H, Dh] over key parts as
    `stage_cache.cache_update_and_read` hands them with `unread`: a cached
    window as a `Window`, the call's rows `[B, S, G, Dh]`; `keeps` a [Q, K] a
    part. A KV group at a time: its lanes of the window are read when the
    group before is done (each read waits for that group's context, through
    an `optimization_barrier`), so one group's slices of a long window are
    live at a time and not all of them, which a span's loop over chunks of
    queries would otherwise hold from its start (2.1 GB a full layer in the
    cell); within a group the queries in chunks whose scores stay under
    `decoder.SCORE_BYTES`. -> [B, Q, H, Dh]."""
    b, n_q, h, hd = q.shape
    groups = ks[-1].shape[2]
    n_keys = sum(keep.shape[1] for keep in keeps)
    chunk = decoder.query_chunk(n_q, b * (h // groups) * n_keys * 4)
    q = q.reshape(b, n_q, groups, h // groups, hd)
    out, done = [], 0
    for grp in range(groups):
        def mine(part, grp=grp, done=done):
            if isinstance(part, Window):
                return read_window(part.buf, part.layer + done, part.width,
                                   slice(grp * hd, (grp + 1) * hd)
                                   ).astype(q.dtype)
            return part[:, :, grp]

        k_g, v_g = [mine(k) for k in ks], [mine(v) for v in vs]
        ctx = decoder.map_query_chunks(
            lambda queries, masks, k_g=k_g, v_g=v_g: _attend_group(
                queries[0], k_g, v_g, masks),
            chunk, (q[:, :, grp],), tuple(keeps))
        # `done` is zero, and known only when this group's context is
        ctx, done = jax.lax.optimization_barrier((ctx, jnp.int32(0)))
        out.append(ctx)
    return jnp.stack(out, axis=2).reshape(b, n_q, h, hd)


def _project(p: Dict, normed, q_pos, cfg: TransformerConfig, sliding: bool):
    """q [B, S, H, Dh], k, v [B, S, G, Dh] of `normed` [B, S, D] at positions
    `q_pos` [S], normed a head and rotated by the layer's scheme, and the
    gate a head [B, S, H] where the block has one (None where it has
    none)."""
    b, s, _ = normed.shape
    eps, hd, groups = cfg.layer_norm_eps, cfg.head_dim, cfg.kv_heads
    heads = heads_of(cfg, "sliding" if sliding else "full")
    if p["q"]["w"].shape[0] != heads * hd \
            or ("gate" in p and p["gate"]["w"].shape[0] != heads):
        raise ValueError(
            f"a {'sliding' if sliding else 'full'} block of {heads} query "
            f"heads of {hd} was built with q_proj {p['q']['w'].shape}"
            + (f" and g_proj {p['gate']['w'].shape}" if "gate" in p else ""))
    q = in_row_chunks(lambda rows: lin(p["q"]["w"], rows), normed,
                      heads * hd).reshape(b, s, heads, hd)
    k = lin(p["k"]["w"], normed).reshape(b, s, groups, hd)
    v = lin(p["v"]["w"], normed).reshape(b, s, groups, hd)
    gate = jax.nn.sigmoid(lin(p["gate"]["w"], normed)) \
        if "gate" in p else None                              # [B, S, H]
    q = rotate(rms_norm(p["q_norm"], q, eps), q_pos, cfg, sliding)
    k = rotate(rms_norm(p["k_norm"], k, eps), q_pos, cfg, sliding)
    return q, k, v, gate


def attention(p: Dict, normed, bcache, pos, cfg: TransformerConfig,
              prefill: bool, read_len=None):
    """GQA of `normed` [B, S, D] at [pos, pos + S) over what its layer's
    leaves hold below `pos` and its own rows, gated a head where the block
    has a gate. -> (out, the cache with the rows recorded, counts int32 [3]:
    the window layers' `swa_positions_read`, `swa_positions_live`,
    `swa_ring_wraps`)."""
    b, s, _ = normed.shape
    sliding = "k_ring" in bcache.stack
    q, k, v, gate = _project(p, normed, jnp.asarray(pos) + jnp.arange(s),
                             cfg, sliding)
    leaves = dict(window=cfg.sliding_window, names=("k_ring", "v_ring"),
                  ring=True) if sliding else dict(read_len=read_len)
    ks, vs, keeps, bcache = cache_update_and_read(
        bcache, k, v, pos, prefill, s, normed.dtype, unread=True, **leaves)
    counts = jnp.zeros(3, jnp.int32)
    if sliding:
        ring = bcache.stack["k_ring"].shape[2]
        counts = jnp.stack([
            jnp.int32(b * sum(keep.size for keep in keeps)),
            b * sum(jnp.sum(keep, dtype=jnp.int32) for keep in keeps),
            (jnp.asarray(pos) % ring + s > ring).astype(jnp.int32)])
    ctx = attend(q, ks, vs, keeps)
    if gate is not None:
        ctx = ctx * gate[..., None]
    return lin(p["attn_out"]["w"], ctx.reshape(b, s, -1)), bcache, counts


def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """Cached block (the decode driver's `_block_step` contract) of any of
    the kinds. The rows of `x` sit at [pos, pos + S): a full block attends
    the cached window below `pos`, a sliding block its ring, both their own
    rows, and their keys and values are recorded for `write_rows`."""
    eps = cfg.layer_norm_eps
    mixed, bcache, counts = attention(
        p, rms_norm(p["ln_before"], x, eps), bcache, pos, cfg, prefill,
        read_len)
    h = x + mixed
    delta, moe = decoder.ffn(p, rms_norm(p["ln_after"], h, eps), cfg)
    return h + delta, bcache._replace(
        rows=dict(bcache.rows, stats=jnp.concatenate([moe, counts])))


def rows_block_step(p: Dict, x, bcache, at: RowsAt, cfg: TransformerConfig,
                    block: int):
    """`cached_block_step` for one token a row, row r at `at.pos[r]` (the
    served executor's step, parallel/decode_rows.py), of any of the kinds:
    each row's q and k are rotated at its own position (the rows taken as
    one sequence of R positions, which is what `rotate` turns); a full block
    walks its slots' rows to the furthest live row, a sliding block its
    slots' rings, each slot masked by the position it holds FOR ITS ROW
    (`stage_cache.attend_rows`). A dead row is routed to no expert and adds
    to no count."""
    eps, rows = cfg.layer_norm_eps, x.shape[0]
    sliding = "k_ring" in bcache.stack
    normed = rms_norm(p["ln_before"], x, eps)
    q, k, v, gate = (
        None if y is None else y.reshape((rows, 1) + y.shape[2:])
        for y in _project(p, normed.reshape(1, rows, -1), at.pos, cfg,
                          sliding))
    window = cfg.sliding_window if sliding else 0
    ctx, bcache = attend_rows(
        bcache, q, k, v, at, block, cfg, window=window,
        names=("k_ring", "v_ring") if sliding else ("k", "v"),
        precision=_ATTENTION, ring=sliding)
    counts = jnp.zeros(3, jnp.int32)
    if sliding:     # a live row's ring as walked and its own row
        ring = bcache.stack["k_ring"].shape[2]
        counts = jnp.stack([
            jnp.sum(at.live, dtype=jnp.int32)
            * (rows_walked(at.reach, ring, block) + 1),
            jnp.sum(jnp.where(at.live, jnp.minimum(at.pos + 1, window), 0),
                    dtype=jnp.int32),
            jnp.int32(0)])
    if gate is not None:
        ctx = (ctx.reshape(q.shape) * gate[..., None]).reshape(ctx.shape)
    h = x + lin(p["attn_out"]["w"], ctx)
    delta, moe = decoder.ffn(p, rms_norm(p["ln_after"], h, eps), cfg,
                             live=at.live)
    return h + delta, bcache._replace(
        rows=dict(bcache.rows, stats=jnp.concatenate([moe, counts])))


FAMILY = FamilySpec(name="laguna", cached_block_step=cached_block_step,
                    rows_block_step=rows_block_step,
                    **decoder.token_hooks("laguna", ACTIVATIONS, rms_norm),
                    decoder_model=True, position_dependent_attention=True,
                    cache_leaves=cache_leaves, prefill_span=prefill_span,
                    whole_leaves=("experts",), stats_names=STATS,
                    block_kind=block_kind)


# -- loading -------------------------------------------------------------------

def _assemble(cfg: TransformerConfig, shard_config: ShardConfig, get,
              dtype) -> Dict:
    """Shard params from `get(key, shape)`, a tensor of the published
    scheme (module docstring; `decoder.loader`, `assemble_shard`)."""
    d, groups, hd = cfg.hidden_size, cfg.kv_heads, cfg.head_dim
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
        decoder.whole_blocks(cfg.model_type, subs)
        root = f"model.layers.{block_id}."
        att = root + "self_attn."
        heads = cfg.layer_heads[block_id]
        p = {"ln_before": scale(root + "input_layernorm.weight", d),
             "q": {"w": get(att + "q_proj.weight", (heads * hd, d))},
             "k": {"w": get(att + "k_proj.weight", (groups * hd, d))},
             "v": {"w": get(att + "v_proj.weight", (groups * hd, d))},
             "q_norm": scale(att + "q_norm.weight", hd),
             "k_norm": scale(att + "k_norm.weight", hd),
             "attn_out": {"w": get(att + "o_proj.weight", (d, heads * hd))},
             "ln_after": scale(root + "post_attention_layernorm.weight", d)}
        if cfg.head_gate:
            p["gate"] = {"w": get(att + "g_proj.weight", (heads, d))}
        if block_id < cfg.first_k_dense:
            p["mlp"] = mlp(root + "mlp.", cfg.intermediate_size)
            return p
        p["router"] = {"w": get(root + "mlp.gate.weight",
                                (cfg.n_experts, d)).T}
        held = [mlp(f"{root}mlp.experts.{e}.", cfg.moe_intermediate_size)
                for e in range(first, first + count)]
        p["experts"] = {name: decoder.stack([one[name] for one in held])
                        for name in ("gate", "up", "down")}
        if cfg.n_shared_experts:
            p["shared"] = mlp(
                root + "mlp.shared_expert.",
                cfg.moe_intermediate_size * cfg.n_shared_experts)
            p["shared_gate"] = get(root + "mlp.shared_expert_gate.weight",
                                   (1, d))
        return p

    def get_final() -> Dict:
        return {"ln": scale("model.norm.weight", d),
                "head": {"w": get("lm_head.weight", (cfg.vocab_size, d))}}

    return decoder.assemble_shard(
        shard_config, get_embed, get_block, get_final, dtype,
        kind=lambda block_id: block_kind(cfg, block_id))


load_params, init_params = decoder.loader(_assemble)
