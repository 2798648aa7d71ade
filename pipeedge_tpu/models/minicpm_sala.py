"""MiniCPM-SALA (`model_type` minicpm_sala): a few layers of block-sparse
attention (MiniCPM4's InfLLM-v2: a query scores pooled keys and attends the
blocks it keeps) beside three times as many lightning-attention layers
(linear attention over a state that is a matrix a head, under a fixed decay
a head), every one before a dense SwiGLU FFN, in MiniCPM's scaled trunk.

The block, `x` [B, S, D], plain RMSNorm, no bias anywhere:
  h = x + r Mixer(rms(x; input_layernorm))
  x' = h + r FFN(rms(h; post_attention_layernorm))
`r = cfg.scale_depth / sqrt(cfg.published_layers)`: the depth under the root
is the published model's, whatever the cut. The embedding's rows are times
`cfg.scale_emb` and the head's normed input over `hidden_size /
dim_model_base`: two `factor` leaves the loader writes (`decoder.
token_hooks`). `cfg.layer_types[i]` names block i's mixer ("minicpm4" |
"lightning-attn"), which is its kind (`block_kind`: "sparse" | "lightning").

**Lightning attention**, `H` heads of `Dh` for q, k and v alike: q and k
RMS-normed a head, rotated (halves layout, the whole head, absolute
position); a head's state `S` [Dh, Dh]:
  S_t = lambda_h S_(t-1) + k_t v_t^T;  o_t = q_t S_t / sqrt(Dh)
(`lightning_step`, what a decode step runs), `lambda_h = exp(-s_h)`, `s_h =
2**(-8 h / H) (1 - l / (L - 1) + 1e-5)`, `h` from 1, `l` the layer's index
in the published `L`. The heads' outputs joined, normed over all `H Dh`
lanes, times `sigmoid(o_gate u)`, `o_proj`. A span runs in chunks of `C` =
`cfg.linear_chunk` (`lightning_chunked`):
  O = ((Q K^T) . D) V + Lambda Q S_prev,   D_ij = lambda**(i - j), i >= j
  S_new = lambda**C S_prev + sum_i lambda**(C - 1 - i) k_i v_i^T
with no inverse. Every power of `lambda` is read from a table of a layer,
`decay` [H, C + 1], `lambda**n` for `n` = 0..C, which the loader computes on
the host in float64 from `lambda` as float32 holds it: the decay is a
constant of the model, and the chip's own `exp` is a few 1e-7 off with a
bias that a state compounds over thousands of positions (PERF.md, row 29).

**Block-sparse attention**, `H` query heads, `G` KV heads of `Dh`, no
rotation, q and k RMS-normed a head, scores over `Dh**0.5`, the output times
`sigmoid(o_gate u)` before `o_proj`. `cfg.sparse_attention` = (kernel,
stride, block, topk, init_blocks, window, dense_len). A query at `t <
dense_len` attends every position at or before it. A later one, a KV head
(`block_scores`, `select`):
 1. pooled keys `Kbar_j = mean(k[stride j : stride j + kernel])`, those
    whose last position is at or before `t`;
 2. `p_h = softmax_j(q_h . Kbar_j / sqrt(Dh))` for each of the KV head's `H
    / G` query heads, `s(j)` their sum (0 for a kernel not complete);
 3. a block `m` of `block` positions scores the largest `s(j)` among the
    kernels that overlap it;
 4. kept: the first `init_blocks` blocks, the `window / block` blocks that
    end with the query's own, and the `topk` best of the others at or before
    it (ties to the lower block);
 5. softmax attention over the kept blocks' positions at or before `t`.

**Cache: three geometries in one stage** (`cache_leaves`, models/shard.py
`CacheLeaf`). The sparse blocks own `k`, `v` `[L_sparse, B, T, G*Dh]`, a row
a position, and `k_pool` `[L_sparse, B, T / stride, G*Dh]`, a row every
`stride` positions (models/stage_cache.py, "A stride"): a call writes the
pooled rows its positions complete, the first of them from raw keys of the
call before, which it reads back from `k` (`pooled_rows`). The lightning
blocks own `la_state` `[L_lightning, B, H, Dh, Dh]`, a row a REQUEST, read
and replaced whole by every call.

**What a call reads.** A decode step of a sparse block reads `k_pool` over
the attended width and then ONLY the blocks it keeps, gathered by block
index from `k` and `v` as stored (`gather_blocks`: `init_blocks + window /
block + topk` slots of `block` rows a KV head, whatever the position; 97 x
64 rows at the published sizes, 12.7 MB a row of the batch where the live
window at 64k is 134 MB); below `dense_len` it reads the first `dense_len`
positions instead (`lax.cond`: `pos` is traced). A span reads the attended
window of `k` and `v` whole, a KV head's lanes at a time, and applies the
selection as a mask, as keye's indexer does: with uniform random prompts a
chunk of queries keeps nearly every block between them, so a union gathers
the window, and a gather a query reads each block once a query and feeds the
matrix unit 16 rows (PERF.md, PR 44). The masked softmax itself is
`decoder.attend_masked`: on a TPU a streaming kernel that keeps a chunk's
scores in VMEM and skips the key blocks no query of a tile keeps (the dead
end of the ladder's window, the span's own later rows), elsewhere and for a
step the einsums (PERF.md, PR 45). `sparse_blocks_kept` and
`sparse_blocks_read` count both, a query a KV head: a step reads the slots
it gathers, a span every block at or before the query.

**Precision.** Weights as stored (bfloat16); activations, cache and state
float32: products with weights through `exact_dot`, products of two
activations (q.k, the weights over v, the state's, the pooling) at
`HIGHEST`; a step's state update is float32 multiplications and sums on the
vector unit. The selection is a discrete choice a narrower computation makes
differently from the float32 reference.

**Prefill** runs in spans of `cfg.prefill_chunk` positions (a multiple of
the chunk, the block and the kernel's stride) through the decode-shaped
stage program.

Refused by name: the forward path (`sublayer`), tp, sp and ep meshes, the
int8 cache, `--kv-pages` (a page holds positions of one geometry), the SPMD
wave decoder and speculative verify (a rejected draft would need the state
of an earlier position).

Weight format (`model.layers.N.`): `self_attn.{q,k,v,o}_proj.weight`,
`self_attn.{q,k}_norm.weight`, `self_attn.o_gate.weight` in both kinds,
`self_attn.o_norm.weight` in a lightning layer, `mlp.{gate,up,down}_proj.
weight`, `input_layernorm`, `post_attention_layernorm`; `model.norm`,
`model.embed_tokens`, `lm_head` (untied).
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig, decoder
from .decoder import in_row_chunks, lin
from .layers import (TransformerConfig, rms_norm, rope_frequencies,
                     rotate_halves)
from .shard import CacheLeaf, FamilySpec
from .stage_cache import (attend_width, first_strided_row, read_window,
                          strided_rows)

# what a block step counts into the cache's `stats` leaf, in this order
STATS = ("sparse_blocks_kept", "sparse_blocks_read", "sparse_kernels_scored") \
    + decoder.ATTEND_STATS + (
        "sparse_dense_calls", "pooled_rows_written",
        "lightning_positions_chunked", "lightning_positions_stepped",
        "lightning_state_carries")

# activations, cache and state (module docstring, Precision)
ACTIVATIONS = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST

_KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


class Sparse(NamedTuple):
    """`cfg.sparse_attention`, named (module docstring)."""
    kernel: int
    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int

    @property
    def slots(self) -> int:
        """Blocks a query past `dense_len` may keep."""
        return self.init_blocks + self.window // self.block + self.topk


def sparse_of(cfg: TransformerConfig) -> Sparse:
    sp = Sparse(*cfg.sparse_attention)
    if sp.kernel % sp.stride or sp.block % sp.stride or sp.window % sp.block \
            or sp.kernel < 2 * sp.stride or sp.dense_len < sp.kernel:
        raise ValueError(
            f"block-sparse attention of {sp}: the kernel (two strides or "
            "more) and the block are whole strides, the window whole "
            "blocks, and a query below dense_len needs no pooled key")
    return sp


def prefill_span(cfg: TransformerConfig) -> int:
    return cfg.prefill_chunk


def block_kind(cfg: TransformerConfig, block_id: int) -> str:
    return _KINDS[cfg.layer_types[block_id]]


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """The cache's leaves (module docstring, Cache): what follows `[L, B,
    T]` in the sparse blocks' `k`, `v`, `[L, B, T / stride]` in their pooled
    keys and `[L, B]` in the lightning blocks' state."""
    sp = sparse_of(cfg)
    rows = CacheLeaf((cfg.kv_heads * cfg.head_dim,), ACTIVATIONS, "sparse")
    return {"k": rows, "v": rows,
            "k_pool": rows._replace(stride=sp.stride, reach=sp.kernel),
            "la_state": CacheLeaf(
                (cfg.num_attention_heads, cfg.head_dim, cfg.head_dim),
                ACTIVATIONS, "lightning", whole=True),
            "stats": jax.ShapeDtypeStruct((len(STATS),), jnp.int32)}


def _dots(spec: str, x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.einsum(spec, x, y, precision=_EXACT,
                      preferred_element_type=jnp.float32)


# -- lightning attention -------------------------------------------------------

def decay_table(cfg: TransformerConfig, block_id: int) -> np.ndarray:
    """`lambda_h**n` [H, C + 1] float32 of layer `block_id`, `n` = 0..C
    (module docstring): float64 powers of the decay as float32 holds it."""
    heads, last = cfg.num_attention_heads, max(cfg.published_layers - 1, 1)
    slope = 2.0 ** (-8.0 * np.arange(1, heads + 1, dtype=np.float64) / heads) \
        * (1.0 - block_id / last + 1e-5)
    held = np.exp(-slope).astype(np.float32).astype(np.float64)
    return (held[:, None] ** np.arange(cfg.linear_chunk + 1)).astype(
        np.float32)


def lightning_step(q, k, v, decay, state):
    """One position of the recurrence: q, k, v [B, H, Dh], decay [H, C + 1],
    state [B, H, Dh, Dh], float32, on the vector unit. -> (o [B, H, Dh]
    before its scale, state)."""
    state = state * decay[:, 1][None, :, None, None] \
        + k[..., :, None] * v[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def lightning_chunked(q, k, v, decay, state):
    """The recurrence over a span in chunks of `C` (module docstring): q, k,
    v [B, S, H, Dh], decay [H, C + 1], state [B, H, Dh, Dh], float32. A last
    chunk the span does not fill decays the state by the positions it has.
    -> (o [B, S, H, Dh] before its scale, the state after the span)."""
    b, s, h, hd = q.shape
    chunk = decay.shape[1] - 1
    n = -(-s // chunk)
    filled = np.clip(s - chunk * np.arange(n), 0, chunk)        # [N]
    at = np.arange(chunk)

    def lay(x):     # [B, S, H, Dh] -> [N, B, H, C, Dh], zeros past S
        x = jnp.pad(x, ((0, 0), (0, n * chunk - s), (0, 0), (0, 0)))
        return jnp.moveaxis(x.reshape(b, n, chunk, h, hd), (1, 3), (0, 2))

    def powers(exponent, live):     # decay[h, exponent] where live, else 0
        return jnp.where(live, jnp.take(decay, np.maximum(exponent, 0),
                                        axis=1), 0.0)

    q, k, v = lay(q), lay(k), lay(v)
    within = _dots("nbhck,nbhsk->nbhcs", q, k) * powers(
        at[:, None] - at[None, :], at[:, None] >= at[None, :])[None, None]
    q_in = q * powers(at + 1, True)[None, None, :, :, None]
    # [H, N, C] -> [N, 1, H, C, 1]: what each key still weighs at the
    # chunk's end, and [N, H] what the state before it does
    k_out = k * jnp.moveaxis(powers(
        filled[:, None] - 1 - at[None, :], at[None, :] < filled[:, None]),
        0, 1)[:, None, :, :, None]
    kept = jnp.take(decay, filled, axis=1).T

    def one_chunk(carry, xs):
        within_n, q_n, k_n, v_n, kept_n = xs
        o = _dots("bhck,bhkv->bhcv", q_n, carry) \
            + _dots("bhcs,bhsv->bhcv", within_n, v_n)
        return kept_n[None, :, None, None] * carry \
            + _dots("bhck,bhcv->bhkv", k_n, v_n), o

    state, o = jax.lax.scan(one_chunk, state, (within, q_in, k_out, v, kept))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * chunk, h, hd)
    return o[:, :s], state


def _heads(p: Dict, name: str, normed, heads: int, hd: int):
    b, s, _ = normed.shape
    return in_row_chunks(lambda rows: lin(p[name]["w"], rows), normed,
                         heads * hd).reshape(b, s, heads, hd)


def lightning(p: Dict, normed, state, pos, cfg: TransformerConfig):
    """The lightning mixer of `normed` [B, S, D] at [pos, pos + S) from
    `state` [B, H, Dh, Dh]. -> (out [B, S, D], the state after)."""
    b, s, _ = normed.shape
    heads, hd, eps = cfg.num_attention_heads, cfg.head_dim, cfg.layer_norm_eps
    q_pos = jnp.asarray(pos) + jnp.arange(s)
    freqs = rope_frequencies(hd, cfg.rope_theta)
    q = rotate_halves(rms_norm(p["q_norm"], _heads(p, "q", normed, heads, hd),
                               eps), q_pos, freqs)
    k = rotate_halves(rms_norm(p["k_norm"], _heads(p, "k", normed, heads, hd),
                               eps), q_pos, freqs)
    v = _heads(p, "v", normed, heads, hd)
    gate = jax.nn.sigmoid(_heads(p, "gate", normed, heads, hd))
    decay = p["decay"].astype(jnp.float32)
    if s == 1:
        o, state = lightning_step(q[:, 0], k[:, 0], v[:, 0], decay, state)
        o = o[:, None]
    else:
        o, state = lightning_chunked(q, k, v, decay, state)
    o = rms_norm(p["o_norm"], (o * hd ** -0.5).reshape(b, s, heads * hd), eps)
    return lin(p["attn_out"]["w"], (o * gate.reshape(b, s, -1)).astype(
        normed.dtype)), state


# -- block-sparse attention ----------------------------------------------------

def pooled_rows(k_new, bcache, pos, prefill: bool, sp: Sparse):
    """The rows of `k_pool` a call at [pos, pos + S) hands `write_rows`
    (models/stage_cache.py, "A stride"): k_new [B, S, G*Dh] its keys. Row
    `first + r` is the mean of the `kernel` raw keys from `stride * (first +
    r)`, of which up to `kernel - 1` lie before `pos` and are read back from
    the layer's `k`; a row whose last position the call does not reach is
    handed as the cache holds it. -> (rows [B, n, G*Dh], their first index,
    how many of them the call completed)."""
    b, s, lanes = k_new.shape
    leaf = bcache.stack["k_pool"]
    n = strided_rows(s, sp.stride, leaf.shape[2])
    first = first_strided_row(pos, sp.stride, sp.kernel)
    reach = sp.kernel - 1
    start = jnp.maximum(jnp.asarray(pos) - reach, 0)
    if prefill:
        tail = jnp.zeros((b, reach, lanes), k_new.dtype)
    else:
        tail = jax.lax.dynamic_slice(
            bcache.stack["k"], (bcache.layer, 0, start, 0),
            (1, b, reach, lanes))[0].astype(k_new.dtype)
    # the position each raw key sits at; a tail row at or past `pos` is
    # none of the cache's yet (a call within `kernel` of the start)
    tail_pos = start + jnp.arange(reach)
    at = jnp.concatenate([jnp.where(tail_pos < pos, tail_pos, -1),
                          jnp.asarray(pos) + jnp.arange(s)])
    low = (first + jnp.arange(n)) * sp.stride                       # [n]
    member = (at[None, :] >= low[:, None]) \
        & (at[None, :] < low[:, None] + sp.kernel)
    fresh = _dots("ri,bic->brc", member.astype(jnp.float32) / sp.kernel,
                  jnp.concatenate([tail, k_new], axis=1))
    complete = low + sp.kernel <= jnp.asarray(pos) + s
    old = jax.lax.dynamic_slice(
        leaf, (bcache.layer, 0, first, 0), (1, b, n, lanes))[0]
    rows = jnp.where(complete[None, :, None], fresh.astype(leaf.dtype), old)
    return rows, first, b * jnp.sum(complete, dtype=jnp.int32)


def block_scores(q, pool, t, sp: Sparse, n_blocks: int):
    """Steps 1-3 (module docstring) for ONE KV head: q [B, Q, r, Dh] its
    query heads, pool [B, J, Dh] its pooled keys, t [Q] the queries'
    positions. -> a score a block [B, Q, n_blocks] float32."""
    hd, held = q.shape[-1], pool.shape[1]
    done = (jnp.arange(held) * sp.stride + sp.kernel - 1)[None, :] \
        <= t[:, None]                                               # [Q, J]
    scores = jnp.where(done[None, None],
                       _dots("bqrd,bjd->brqj", q, pool) * hd ** -0.5, -1e30)
    summed = jnp.sum(jnp.where(done[None, None],
                               jax.nn.softmax(scores, axis=-1), 0.0), axis=1)
    # kernel j overlaps block m where ratio m - over < j < ratio (m + 1)
    ratio, over = sp.block // sp.stride, sp.kernel // sp.stride
    summed = jnp.pad(summed, ((0, 0), (0, 0), (
        over - 1, max(n_blocks * ratio - held, 0))))
    return jax.lax.reduce_window(
        summed, 0.0, jax.lax.max, (1, 1, ratio + over - 1), (1, 1, ratio),
        "VALID")[..., :n_blocks]


def kernels_done(t, sp: Sparse, held: int):
    """How many of `held` pooled keys are complete at or before `t`."""
    return jnp.clip((t + 1 - sp.kernel) // sp.stride + 1, 0, held)


def _candidates(t, sp: Sparse, n_blocks: int):
    """Step 4's fixed part for queries at `t` [Q]: (the first blocks, the
    local ones, the others at or before the query), each [Q, n_blocks]."""
    m = jnp.arange(n_blocks)[None, :]
    own = (t // sp.block)[:, None]
    live = m <= own
    first = (m < sp.init_blocks) & live
    local = (m > own - sp.window // sp.block) & live
    return first, local, live & ~first & ~local


def select(scores, t, sp: Sparse):
    """Step 4 as a mask: scores [B, Q, M] (`block_scores`), t [Q]. -> kept
    [B, Q, M] bool."""
    first, local, others = _candidates(t, sp, scores.shape[-1])
    far = jnp.broadcast_to(others[None], scores.shape)
    if sp.topk < scores.shape[-1]:
        ranked = jnp.where(far, scores, -jnp.inf)
        kth = jax.lax.top_k(ranked, sp.topk)[0][..., -1:]
        above = ranked > kth
        level = (ranked == kth) & far
        spare = sp.topk - jnp.sum(above, axis=-1, keepdims=True)
        far = above | (level & (jnp.cumsum(level, axis=-1) <= spare))
    return (first | local)[None] | far


def select_slots(scores, t, sp: Sparse):
    """Step 4 as a list, for a gather: scores [B, Q, M], t [Q]. -> (blocks
    [B, Q, slots] int32, which of them are kept [B, Q, slots]): the first
    blocks, the local ones, the best of the others, each block once."""
    n_blocks = scores.shape[-1]
    _, _, others = _candidates(t, sp, n_blocks)
    best, far = jax.lax.top_k(jnp.where(others[None], scores, -jnp.inf),
                              min(sp.topk, n_blocks))
    own = (t // sp.block)[:, None]
    first = jnp.broadcast_to(jnp.arange(sp.init_blocks)[None], (
        t.shape[0], sp.init_blocks))
    local = own - sp.window // sp.block + 1 + jnp.arange(
        sp.window // sp.block)[None]
    fixed = jnp.concatenate([first, local], axis=1)                 # [Q, .]
    fixed_ok = jnp.concatenate(
        [first <= own, local >= sp.init_blocks], axis=1)
    shape = scores.shape[:1] + fixed.shape
    blocks = jnp.concatenate([jnp.broadcast_to(fixed[None], shape), far], -1)
    kept = jnp.concatenate([jnp.broadcast_to(fixed_ok[None], shape),
                            best > -jnp.inf], -1)
    return jnp.where(kept, blocks, 0).astype(jnp.int32), kept


def gather_blocks(buf, layer, blocks, size: int, hd: int):
    """The rows of `blocks` [B, G, N] from one layer of a stacked leaf `[L,
    B, T, G*Dh]`, KV head g's lanes of block m for entry [., g, .]: `size`
    whole rows of `Dh` lanes a block, as stored. -> [B, G, N * size, Dh]."""
    b, groups, n = blocks.shape

    def one(row, grp, block):
        return jax.lax.dynamic_slice(
            buf, (layer, row, block * size, grp * hd), (1, 1, size, hd))[0, 0]

    rows = jnp.broadcast_to(jnp.arange(b)[:, None, None], blocks.shape)
    grps = jnp.broadcast_to(jnp.arange(groups)[None, :, None], blocks.shape)
    out = jax.vmap(jax.vmap(jax.vmap(one)))(rows, grps, blocks)
    return out.reshape(b, groups, n * size, hd)


def attend_span(q, k_new, v_new, pool, bcache, pos, width: int, sp: Sparse,
                dense: bool):
    """The sparse layer's attention of a span's queries q [B, S, H, Dh] at
    [pos, pos + S) over the cached window [0, width) below `pos` (none where
    `width` is 0) and the span's own rows k_new, v_new [B, S, G, Dh], the
    selection a mask (module docstring, What a call reads); `pool` [B, J,
    G*Dh] the pooled keys with the call's own rows in place; `dense`: no
    query is past `dense_len`, nothing is scored. A KV head at a time, its
    lanes of the window read when the head before is done, the queries in
    chunks whose scores stay under `decoder.SCORE_BYTES`, each chunk one
    softmax over both parts (`decoder.attend_masked`). -> (context [B, S, H,
    Dh], [blocks kept, blocks read, kernels scored, 1 where the attention
    took the streaming kernel])."""
    b, s, h, hd = q.shape
    groups = k_new.shape[2]
    n_blocks = -(-max(width, s) // sp.block)
    chunk = decoder.query_chunk(s, b * (h // groups) * (width + s) * 4)
    q = q.reshape(b, s, groups, h // groups, hd)
    t_all = jnp.asarray(pos) + jnp.arange(s)
    out, counts, done = [], [], 0
    for grp in range(groups):
        lanes = slice(grp * hd, (grp + 1) * hd)
        ks, vs = [k_new[:, :, grp]], [v_new[:, :, grp]]
        if width:
            ks.insert(0, read_window(bcache.stack["k"], bcache.layer + done,
                                     width, lanes).astype(q.dtype))
            vs.insert(0, read_window(bcache.stack["v"], bcache.layer + done,
                                     width, lanes).astype(q.dtype))

        pool_g = None if dense else pool[..., lanes]

        def one_chunk(queries, rows, ks=ks, vs=vs, pool_g=pool_g):
            q_c, (t, own) = queries[0], rows                # [B, Qc, r, Dh]
            live_blocks = t // sp.block + 1
            keeps = [(jnp.arange(s)[None, :] <= own[:, None])[None]]
            if width:
                keeps.insert(0, jnp.broadcast_to(
                    jnp.arange(width) < pos, (1, t.shape[0], width)))
            read_n = b * jnp.sum(live_blocks)
            kept_n, scored = read_n, jnp.int32(0)
            if not dense:
                past = (t >= sp.dense_len)[None, :, None]
                kept = select(block_scores(q_c, pool_g, t, sp, n_blocks),
                              t, sp) | ~past
                spread = jnp.repeat(kept, sp.block, axis=-1)
                keeps[-1] = keeps[-1] & jax.lax.dynamic_slice_in_dim(
                    spread, pos, s, axis=-1)
                if width:
                    keeps[0] = keeps[0] & spread[..., :width]
                kept_n = jnp.sum(jnp.where(
                    past[..., 0], jnp.sum(kept, axis=-1), live_blocks[None]))
                scored = b * jnp.sum(jnp.where(
                    past[0, :, 0], kernels_done(t, sp, pool_g.shape[1]), 0))
            ctx, fused = decoder.attend_masked(q_c, ks, vs, keeps)
            return ctx.astype(q_c.dtype), kept_n.astype(jnp.int32), \
                read_n.astype(jnp.int32), scored.astype(jnp.int32), \
                jnp.int32(fused)

        ctx, *counted = decoder.map_query_chunks(
            one_chunk, chunk, (q[:, :, grp],), (t_all, jnp.arange(s)))
        # `done` is zero, and known only when this head's context is: the
        # next head's window is read then, not at the call's start
        ctx, done = jax.lax.optimization_barrier((ctx, jnp.int32(0)))
        out.append(ctx)
        counts.append(jnp.stack([jnp.sum(c) for c in counted]))
    counts = sum(counts)
    return jnp.stack(out, axis=2).reshape(b, s, h, hd), \
        counts.at[3].min(1)


def attend_step(q, k_new, v_new, pool, bcache, pos, width: int, sp: Sparse):
    """The sparse layer's attention of ONE query a row, q [B, 1, H, Dh] at
    `pos`, over its kept blocks alone (module docstring, What a call reads):
    every KV head's blocks in one gather a leaf. -> (context [B, 1, H, Dh],
    [blocks kept, blocks read, kernels scored, 0: the einsums])."""
    b, _, h, hd = q.shape
    groups = k_new.shape[2]
    t = jnp.asarray(pos).reshape(1)
    q = q.reshape(b, groups, h // groups, hd)                   # [B, G, r, Dh]
    own_k, own_v = k_new[:, 0, :, None], v_new[:, 0, :, None]   # [B, G, 1, Dh]
    n_blocks = -(-width // sp.block)
    scores = jnp.stack([block_scores(
        q[:, None, grp], pool[..., grp * hd:(grp + 1) * hd], t, sp,
        n_blocks)[:, 0] for grp in range(groups)], axis=1)      # [B, G, M]
    blocks, kept = (x.reshape(b, groups, -1) for x in select_slots(
        scores.reshape(b * groups, 1, n_blocks), t, sp))        # [B, G, N]
    at = (blocks[..., None] * sp.block + jnp.arange(sp.block)).reshape(
        b, groups, -1)
    keep = jnp.repeat(kept, sp.block, axis=-1) & (at < pos)
    cached_k, cached_v = (gather_blocks(
        bcache.stack[name], bcache.layer, blocks, sp.block, hd
        ).astype(q.dtype) for name in ("k", "v"))
    mixed, total = decoder.softmax_over(
        q, [cached_k, own_k], [cached_v, own_v],
        [keep[:, :, None], jnp.ones((1, 1, 1, 1), bool)], "bgrd,bgkd->bgrk")
    counts = jnp.stack([
        jnp.sum(kept), jnp.int32(kept.size),
        b * groups * kernels_done(t[0], sp, pool.shape[1]), 0]).astype(
            jnp.int32)
    return (mixed / total[..., None]).astype(q.dtype).reshape(b, 1, h, hd), \
        counts


def sparse_attention(p: Dict, normed, bcache, pos, cfg: TransformerConfig,
                     prefill: bool, read_len=None):
    """The sparse mixer of `normed` [B, S, D] at [pos, pos + S). -> (out [B,
    S, D], the rows k, v [B, S, G*Dh] and k_pool for the cache, counts int32
    [6]: `STATS`' first six)."""
    b, s, _ = normed.shape
    heads, groups, hd = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    eps, sp = cfg.layer_norm_eps, sparse_of(cfg)
    stack = bcache.stack
    held = stack["k"].shape[2]
    if held % sp.block:
        raise ValueError(
            f"the minicpm_sala family gathers whole blocks of {sp.block} "
            f"positions: a cache of {held} cuts the last one")
    q = rms_norm(p["q_norm"], _heads(p, "q", normed, heads, hd), eps)
    k = rms_norm(p["k_norm"], lin(p["k"]["w"], normed).reshape(
        b, s, groups, hd), eps)
    v = lin(p["v"]["w"], normed).reshape(b, s, groups, hd)
    gate = jax.nn.sigmoid(_heads(p, "gate", normed, heads, hd))
    # through the cache's dtype, as if read back from it
    k = k.astype(stack["k"].dtype).astype(normed.dtype)
    v = v.astype(stack["v"].dtype).astype(normed.dtype)
    k_rows, v_rows = k.reshape(b, s, -1), v.reshape(b, s, -1)
    pool_rows, first, written = pooled_rows(k_rows, bcache, pos, prefill, sp)
    width = 0 if prefill else attend_width(bcache, read_len)
    # no query of the call is past dense_len, whatever `pos`
    dense = (s if prefill else held if read_len is None
             else min(read_len, held)) <= sp.dense_len
    pool = None
    if not dense:       # the pooled keys with this call's rows in place
        pool = pool_rows if prefill else jax.lax.dynamic_update_slice(
            read_window(stack["k_pool"], bcache.layer, width // sp.stride),
            pool_rows.astype(stack["k_pool"].dtype), (0, first, 0)
        ).astype(normed.dtype)
    if s > 1 or dense:
        ctx, counts = attend_span(q, k, v, pool, bcache, pos, width, sp,
                                  dense)
    else:
        short = min(width, sp.dense_len)
        ctx, counts = jax.lax.cond(
            jnp.asarray(pos) < sp.dense_len,
            lambda: attend_span(q, k, v, None, bcache, pos, short, sp, True),
            lambda: attend_step(q, k, v, pool, bcache, pos, width, sp))
    counts = jnp.concatenate([counts, jnp.stack(
        [(jnp.asarray(pos) + s <= sp.dense_len).astype(jnp.int32), written])])
    out = lin(p["attn_out"]["w"], (ctx * gate).reshape(
        b, s, heads * hd).astype(normed.dtype))
    return out, {"k": k_rows, "v": v_rows, "k_pool": pool_rows}, counts


# -- the family's hooks --------------------------------------------------------

def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """Cached block (the decode driver's `_block_step` contract) of either
    kind. The rows of `x` sit at [pos, pos + S). A sparse block records its
    keys, values and the pooled keys it completed for `write_rows`; a
    lightning block takes its state from the cache (a prefill, at `pos` 0:
    zeros) and records what it is after the span, which takes its place."""
    b, s, _ = x.shape
    eps = cfg.layer_norm_eps
    depth = cfg.scale_depth / cfg.published_layers ** 0.5
    normed = rms_norm(p["ln_before"], x, eps)
    if "decay" in p:
        state = jax.lax.dynamic_index_in_dim(
            bcache.stack["la_state"], bcache.layer, 0, keepdims=False)
        if prefill:
            state = jnp.zeros_like(state)
        mixed, state = lightning(p, normed, state.astype(jnp.float32), pos,
                                 cfg)
        rows = {"la_state": state}
        counts = jnp.array([0] * 6 + [b * s if s > 1 else 0,
                                      b if s == 1 else 0,
                                      0 if prefill else 1], jnp.int32)
    else:
        mixed, rows, counts = sparse_attention(p, normed, bcache, pos, cfg,
                                               prefill, read_len)
        counts = jnp.concatenate([counts, jnp.zeros(3, jnp.int32)])
    h = x + depth * mixed
    out = h + depth * decoder.dense_ffn(
        p["mlp"], rms_norm(p["ln_after"], h, eps))
    return out, bcache._replace(rows=dict(rows, stats=counts))


FAMILY = FamilySpec(name="minicpm_sala", cached_block_step=cached_block_step,
                    **decoder.token_hooks("minicpm_sala", ACTIVATIONS,
                                          rms_norm),
                    decoder_model=True, position_dependent_attention=True,
                    cache_leaves=cache_leaves, prefill_span=prefill_span,
                    stats_names=STATS, block_kind=block_kind)


# -- loading -------------------------------------------------------------------

def _assemble(cfg: TransformerConfig, shard_config: ShardConfig, get,
              dtype) -> Dict:
    """Shard params from `get(key, shape)`, a tensor of the published
    scheme (module docstring; `decoder.loader`, `assemble_shard`), with the
    constants the configuration gives: a lightning layer's `decay` table and
    the two `factor`s of the trunk, kept float32."""
    d, heads, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    sparse_of(cfg)

    def scale(key, n):
        return {"scale": get(key, (n,))}

    def get_embed() -> Dict:
        return {"wte": get("model.embed_tokens.weight", (cfg.vocab_size, d)),
                "factor": np.float32(cfg.scale_emb)}

    def get_block(block_id: int, subs: tuple) -> Dict:
        decoder.whole_blocks("minicpm_sala", subs)
        root = f"model.layers.{block_id}."
        att = root + "self_attn."
        sparse = block_kind(cfg, block_id) == "sparse"
        groups = cfg.kv_heads if sparse else heads
        p = {"ln_before": scale(root + "input_layernorm.weight", d),
             "q": {"w": get(att + "q_proj.weight", (heads * hd, d))},
             "k": {"w": get(att + "k_proj.weight", (groups * hd, d))},
             "v": {"w": get(att + "v_proj.weight", (groups * hd, d))},
             "gate": {"w": get(att + "o_gate.weight", (heads * hd, d))},
             "q_norm": scale(att + "q_norm.weight", hd),
             "k_norm": scale(att + "k_norm.weight", hd),
             "attn_out": {"w": get(att + "o_proj.weight", (d, heads * hd))},
             "ln_after": scale(root + "post_attention_layernorm.weight", d),
             "mlp": {name: get(f"{root}mlp.{name}_proj.weight", shape)
                     for name, shape in (
                         ("gate", (cfg.intermediate_size, d)),
                         ("up", (cfg.intermediate_size, d)),
                         ("down", (d, cfg.intermediate_size)))}}
        if not sparse:
            p["o_norm"] = scale(att + "o_norm.weight", heads * hd)
            p["decay"] = decay_table(cfg, block_id)
        return p

    def get_final() -> Dict:
        return {"ln": scale("model.norm.weight", d),
                "head": {"w": get("lm_head.weight", (cfg.vocab_size, d))},
                "factor": np.float32(cfg.dim_model_base / d)}

    return decoder.assemble_shard(
        shard_config, get_embed, get_block, get_final, dtype,
        kind=lambda block_id: block_kind(cfg, block_id),
        float32=(("decay",), ("factor",)))


load_params, init_params = decoder.loader(_assemble)
