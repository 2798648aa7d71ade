"""A stage's cache as a block step may see it: the leaves' geometry, the view
a block step is handed of them, and the read every plain attention shares.

This is the seam between a decoder family (`models/<family>.py`: its
`cache_leaves`, its `cached_block_step`) and the decode drivers
(`DecodePipeline`'s module, the SPMD wave decoder, `kv/pool.py`), which make
the cache, hand each block its layer of it and write the rows the block
recorded. A family imports this module and nothing of `parallel/`; the
drivers import it like any other user. What stage programs do with a cache
(donate it, bucket the attended window, pick the int8 kernel) is theirs.
"""
from __future__ import annotations

from functools import reduce
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import TransformerConfig


Cache = Dict[str, jax.Array]   # {'k': [L, B, T, H*Dh], 'v': [L, B, T, H*Dh]}
# The heads are folded into the last axis (H is `cfg.kv_heads`, head g in
# lanes [g*Dh, (g+1)*Dh)). The TPU compiler chooses a leaf's layout from its
# shape: `[L, B, T, H, Dh]` with Dh = 64 it stores positions minor-most in
# tiles of 128 (a 64-wide minor axis would be padded to 128 lanes), and a
# decode step's one position is then one lane of every tile it touches. The
# folded leaf's minor axis is whole lanes, so it is kept as declared, a
# position is a row, and `write_rows` is one row update a leaf
# (docs/DECODE.md). The attention reads the window in this form (`attend`).
# int8 variant adds per-(block, batch, position, head) scale/shift rows —
# the head axis shards over 'tp' with the K/V buffers:
#   {'k': int8, 'v': int8, 'k_scale'/'k_shift'/'v_scale'/'v_shift': [L, B, T, H]}
#
# Inside a stage program a block step is handed a `LayerCache` (the whole
# stack and its layer's index), and only
# `cache_update_and_read` and `cache_write_quantized` open it: they read
# the attended window of that layer and record the new rows, which
# `_run_blocks` writes into the stack once the scan is done. A step moves
# the new rows and the window, nothing else (docs/DECODE.md).
#
# A family may name further leaves (`FamilySpec.cache_leaves`: an indexer's
# keys beside `k` and `v`), shaped `[L, B, T, ...]` like these and written
# and read by the same two functions. One leaf is not rows: `stats`, `[L, n,
# 2]` int32, the counts a family's block steps add up on the device (a block
# step's `rows["stats"]` is `[n]` int32, what this call counted). A count is
# kept as (units of 2**20, remainder) so that it passes 2**31; the host reads
# the leaf once a batch (`read_stats`).
#
# A leaf may say more (`models/shard.py` `CacheLeaf`). Its `kind` is the kind
# of block that owns it, or the kinds where more than one do: the leaf's `L`
# is then the count of those kinds in the stage, a run of blocks is handed the
# leaves its kind owns beside those no kind does, and its blocks index the
# layers of the kinds that share its leaves (`_run_blocks`).
# `whole` makes it a row a request, `[L, B, ...]` with no position axis: a
# recurrent state, which a call reads, and replaces whole where the others
# are written at `pos` (docs/DECODE.md, "Two geometries").
#
# A ring. A leaf with a `length` keeps that many positions whatever the
# stage's `max_len`: `[L, B, W, ...]`, position `p` at slot `p mod W`
# (`init_cache`, `W = min(length, max_len)`). A call's rows are written where
# they fall, around the ring's end where they straddle it (`write_rows`);
# the ring is read whole, as stored, never rolled or copied into position
# order, and a slot is masked by the position it holds: before a call at
# `pos`, slot `s` holds the largest `p < pos` with `p mod W == s`, nothing
# while that is negative (`cache_update_and_read`, `ring=True`). The ladder
# (`attend_bucket`) does not reach it: it reads `W` at every position.
#
# A stride. A leaf with a `stride` keeps one row every `stride` positions,
# `[L, B, max_len // stride, ...]`: row `j` summarises the `reach` positions
# from `stride * j` (the mean of their keys) and exists once the last of them
# is written, so a call at `[pos, pos + S)` completes the rows from
# `first_strided_row(pos)` on, `strided_rows(S)` of them at most, some from
# positions of the call before. The block step hands exactly that many, those
# it does not complete as the cache held them, and `write_rows` puts them
# there; a reader masks row `j` by `stride * j + reach - 1 <= ` its query.
STATS = "stats"
_STATS_UNIT = 20


def leaf_owners(leaves) -> Dict:
    """{leaf: the kinds of block that own it} of the leaves that say."""
    kinds = {name: getattr(leaf, "kind", None)
             for name, leaf in (leaves or {}).items()}
    return {name: kind if isinstance(kind, tuple) else (kind,)
            for name, kind in kinds.items() if kind is not None}


def whole_names(leaves) -> tuple:
    """The leaves that are a row a request and replaced whole."""
    return tuple(name for name, leaf in (leaves or {}).items()
                 if getattr(leaf, "whole", False))


def ring_names(leaves) -> tuple:
    """The leaves that keep a ring of positions, not `max_len` of them."""
    return tuple(name for name, leaf in (leaves or {}).items()
                 if getattr(leaf, "length", 0))


def stride_names(leaves) -> Dict:
    """{leaf: (stride, reach)} of the leaves that keep a row every `stride`
    positions."""
    return {name: (leaf.stride, leaf.reach)
            for name, leaf in (leaves or {}).items()
            if getattr(leaf, "stride", 0)}


def first_strided_row(pos, stride: int, reach: int):
    """The first row of a strided leaf that a call at `pos` can complete:
    the least `j >= 0` whose last position `stride * j + reach - 1` is at or
    past `pos`."""
    return jnp.maximum(-((reach - 1 - jnp.asarray(pos)) // stride), 0)


def strided_rows(span: int, stride: int, held: int) -> int:
    """How many rows of a strided leaf of `held` rows a call of `span`
    positions hands `write_rows`: every row it could complete."""
    return min(-(-span // stride), held)


def shares_layers(owner: Dict, kind) -> tuple:
    """The kinds of block whose layers a leaf of `kind`'s counts (`owner`:
    `leaf_owners`'s), `kind` among them; () where `kind` owns no leaf. A block
    step has one layer index for all it reads, so the leaves a kind owns
    have to belong to the same kinds."""
    sharing = {kinds for kinds in owner.values() if kind in kinds}
    if len(sharing) > 1:
        raise ValueError(
            f"the leaves that blocks of kind {kind!r} own belong to "
            f"different sets of kinds, {sorted(sharing)}: one block step "
            "has one layer index")
    return next(iter(sharing), ())


class LayerSlice(NamedTuple):
    """Stacked block leaves `[L, ...]` a family asked to be handed whole
    (`FamilySpec.whole_leaves`) and the layer the block step may read: it
    slices what it needs, and no whole layer is copied out of the stack."""
    stack: Dict
    layer: jax.Array


class LayerCache(NamedTuple):
    """What a block step holds of its stage's cache: the stacked buffers
    (leaves `[L, B, T, ...]`, as they were before this step), the index of
    the one layer it may read, and, once a cache function has run, the
    `rows` (`[B, S, ...]` a leaf) this step writes at `[pos, pos + S)`.
    `placed` names the leaves whose layer this step may instead write where
    it lies (a decode step's `whole` leaves that the driver has written a
    run at a time, `parallel/decode.py::WHOLE_IN_PLACE_BYTES`): a block that
    does hands back `stack` with that leaf's stack replaced by the updated
    one, and no rows for it."""
    stack: Cache
    layer: jax.Array
    rows: Optional[Cache] = None
    placed: tuple = ()


class Window(NamedTuple):
    """Positions [0, width) of one layer of a stacked leaf, not read yet
    (`cache_update_and_read`, `unread`): `read_window`'s arguments."""
    buf: jax.Array
    layer: jax.Array
    width: int


def read_window(buf: jax.Array, layer, width: int,
                lanes: Optional[slice] = None) -> jax.Array:
    """Positions [0, width) of one layer of stacked `buf` -> [B, width, ...];
    `lanes` keeps that slice of the last axis (one head of a leaf that folds
    its heads into it)."""
    start = (layer,) + (0,) * (buf.ndim - 1)
    sizes = (1, buf.shape[1], width) + buf.shape[3:]
    if lanes is not None:
        start = start[:-1] + (lanes.start,)
        sizes = sizes[:-1] + (lanes.stop - lanes.start,)
    return jax.lax.dynamic_slice(buf, start, sizes)[0]


def write_rows(cache: Cache, rows: Cache, pos, whole: tuple = (),
               rings: tuple = (), strides=None) -> Cache:
    """Every layer's new `rows` (leaves `[L, B, S, ...]`) into the stacked
    cache at positions [pos, pos + S): one in-place update a leaf, for a
    decode step, a span and a prefill alike. A leaf named in `whole` has no
    positions: its rows `[L, B, ...]` take the place of what was there. A
    leaf named in `rings` takes row i at slot `(pos + i) mod W`: one update
    for a step, and for a span two windows of S slots read, merged and
    written back, the one that ends no later than the ring does and the
    one at the ring's start, which takes the rows that ran past its end
    (none, as a rule). Of a span longer than the ring (a whole prompt
    through the prefill program) the last W rows are written. A leaf named
    in `strides` ({name: (stride, reach)}, `stride_names`) takes its rows
    from `first_strided_row(pos)` on ("A stride", above)."""
    def write(buf, new, at=pos):
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype), (0, 0, at) + (0,) * (buf.ndim - 3))

    def strided(name):
        return lambda buf, new: write(
            buf, new, first_strided_row(pos, *strides[name]))

    def around(buf, new):
        ring, span, first = buf.shape[2], new.shape[2], pos
        new = new.astype(buf.dtype)
        if span > ring:
            new, first, span = new[:, :, span - ring:], pos + span - ring, ring
        start = first % ring
        if span == 1:
            return jax.lax.dynamic_update_slice(
                buf, new, (0, 0, start) + (0,) * (buf.ndim - 3))
        at = jnp.arange(span).reshape((span,) + (1,) * (buf.ndim - 3))
        spare = jnp.zeros_like(new)

        def merge(buf, low, rows, mine):   # slots [low, low + S) <- rows
            old = jax.lax.dynamic_slice_in_dim(buf, low, span, axis=2)
            return jax.lax.dynamic_update_slice_in_dim(
                buf, jnp.where(mine, rows, old), low, axis=2)

        # slot low + j takes row j - shift, where there is one
        low = jnp.minimum(start, ring - span)
        shift = start - low
        buf = merge(buf, low, jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([spare, new], axis=2), span - shift, span,
            axis=2), at >= shift)
        # the rows past the ring's end: slot j takes row j + (W - start)
        inside = jnp.minimum(ring - start, span)
        return merge(buf, 0, jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([new, spare], axis=2), inside, span, axis=2),
            at < span - inside)

    def replace(buf, new):
        assert new.shape == buf.shape, (new.shape, buf.shape)
        return new.astype(buf.dtype)

    return {name: buf if not buf.shape[0] else
            (add_stats if name == STATS else
             replace if name in whole else
             around if name in rings else
             strided(name) if name in (strides or ()) else
             write)(buf, rows[name])
            for name, buf in cache.items()}


def add_stats(buf: jax.Array, new: jax.Array) -> jax.Array:
    """The `stats` leaf `[L, n, 2]` with a call's counts `[L, n]` added."""
    low = buf[..., 1] + new
    return jnp.stack([buf[..., 0] + (low >> _STATS_UNIT),
                      low & ((1 << _STATS_UNIT) - 1)], axis=-1)


def merge_stats(total: jax.Array, counted: jax.Array) -> jax.Array:
    """Two `stats` leaves `[L, n, 2]` added, pair by pair."""
    return add_stats(total, counted[..., 1]).at[..., 0].add(counted[..., 0])


def read_stats(cache: Cache):
    """The `stats` leaf's counts as whole numbers on the host, summed over
    the layers: an int64 [n]."""
    import numpy as np
    pairs = np.asarray(cache[STATS]).astype(np.int64)
    return (pairs[..., 0] * (1 << _STATS_UNIT) + pairs[..., 1]).sum(axis=0)


def init_cache(cfg: TransformerConfig, n_blocks: int, batch: int,
               max_len: int, dtype=jnp.float32,
               cache_bits: int = 0, leaves=None, runs=None) -> Cache:
    """Zeroed stacked KV cache for `n_blocks` blocks.

    `leaves` ({name: ShapeDtypeStruct of what follows [L, B, T]}, a
    family's `cache_leaves(cfg)`) replaces the plain `k`, `v` pair; its
    `stats` entry sizes the counters' leaf. Where a leaf is a `CacheLeaf`
    that names the kind (or kinds) of block that owns it, `runs`
    (`kind_runs`: the stage's blocks as `(kind, count)`) gives its `L`, the
    count of those kinds among the `n_blocks`; a `whole` leaf has no `T`,
    one with a `length` keeps `min(length, max_len)` positions, a ring, and
    one with a `stride` a row every `stride` positions.

    `cache_bits=8` stores K/V as int8 with per-(position, head) affine
    scales (QuantPipe's activation-compression idea applied to the decode
    cache): cache reads dominate decode-step HBM traffic, so int8 halves
    the bandwidth bound vs bfloat16 at negligible logit error. Scales are
    per HEAD (not per position only) so the scale rows carry a head axis
    and shard over 'tp' exactly like the K/V buffers — int8 caches
    compose with tensor parallelism in decode, and the finer granularity also
    tightens the quantization error.

    The heads are `cfg.kv_heads` — equal to the query head count for
    every family except GQA decoders (llama), whose cache is kv_heads/
    num_attention_heads times smaller (the point of GQA) — folded with
    the head width into the last axis (the `Cache` comment above)."""
    if leaves is not None:
        if cache_bits:
            raise NotImplementedError(
                "the int8 cache route covers the plain k, v cache only")
        owner, whole = leaf_owners(leaves), whole_names(leaves)
        if owner and sum(n for _, n in runs or ()) != n_blocks:
            raise ValueError(
                f"leaves {sorted(owner)} belong to kinds of block: "
                f"init_cache needs the stage's runs of kinds, got {runs} "
                f"for {n_blocks} blocks")

        def layers(name):
            if name not in owner:
                return n_blocks
            return sum(n for kind, n in runs if kind in owner[name])

        return {name: jnp.zeros((n_blocks,) + tail.shape + (2,), tail.dtype)
                if name == STATS else
                jnp.zeros((layers(name), batch)
                          + (() if name in whole else
                             (min(getattr(tail, "length", 0) or max_len,
                                  max_len)
                              // (getattr(tail, "stride", 0) or 1),))
                          + tuple(tail.shape), tail.dtype)
                for name, tail in leaves.items()}
    shape = (n_blocks, batch, max_len, cfg.kv_heads * cfg.head_dim)
    if cache_bits == 0:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if cache_bits != 8:
        raise ValueError(f"cache_bits must be 0 (off) or 8, got {cache_bits}")
    rows = shape[:3] + (cfg.kv_heads,)     # [..., T, H] per-head scales
    cache = {"k": jnp.zeros(shape, jnp.int8),
             "v": jnp.zeros(shape, jnp.int8)}
    for t in ("k", "v"):
        cache[f"{t}_scale"] = jnp.zeros(rows, jnp.float32)
        cache[f"{t}_shift"] = jnp.zeros(rows, jnp.float32)
    return cache


def quantize_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Affine-quantize [B, S, H, Dh] to int8 per (batch, position, head)."""
    lo = jnp.min(x, axis=3).astype(jnp.float32)             # [B, S, H]
    hi = jnp.max(x, axis=3).astype(jnp.float32)
    scale = jnp.maximum(hi - lo, 1e-8) / 255.0
    q = jnp.round((x.astype(jnp.float32) - lo[..., None])
                  / scale[..., None]) - 128.0
    return q.astype(jnp.int8), scale, lo


def dequantize_rows(q: jax.Array, scale: jax.Array, shift: jax.Array,
                    dtype) -> jax.Array:
    """Invert `quantize_rows`: [B, T, H, Dh] int8 + [B, T, H] -> dtype."""
    return ((q.astype(jnp.float32) + 128.0) * scale[..., None]
            + shift[..., None]).astype(dtype)

def _parts(x) -> tuple:
    """`attend` takes its keys in one part or several: a bare array is one."""
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


# columns of one MXU pass: a product with fewer is padded to these. On the
# chip (gpt2-medium, 32 rows, 512 window; PERF.md, PR 32) a span's step over
# the stored window took 10.3 ms at 128 query columns and 19.1 at 256, with
# the window copied heads apart 14.6 and 17.9
_MXU_COLUMNS = 128


def _fold(x: jax.Array) -> jax.Array:
    """[B, S, H, Dh] -> [B, S, H*Dh]: the heads into the last axis, as a
    cache leaf stores them."""
    return x.reshape(x.shape[:2] + (-1,))


def _scores(q: jax.Array, k_part: jax.Array, precision=None) -> jax.Array:
    """q [B,S,H,Dh] against one part's keys -> [B,H,S,T] float32."""
    b, s, h, hd = q.shape
    stored = k_part.ndim == 3
    g = k_part.shape[2] // hd if stored else k_part.shape[2]
    q = q.reshape(b, s, g, h // g, hd)
    if stored:      # the queries in blocks, column (h, s)
        blocks = jnp.einsum("bqgrd,cg->bcdgrq", q, jnp.eye(g, dtype=q.dtype))
        part = jnp.einsum("bkc,bcn->bnk", k_part,
                          blocks.reshape(b, g * hd, h * s),
                          preferred_element_type=jnp.float32,
                          precision=precision)
    else:
        part = jnp.einsum("bqgrd,bkgd->bgrqk", q, k_part,
                          preferred_element_type=jnp.float32,
                          precision=precision)
    return part.reshape(b, h, s, -1)


def _context(probs: jax.Array, v_part: jax.Array, hd: int,
             precision=None) -> jax.Array:
    """probs [B,H,S,T] over one part's values -> [B,S,H,Dh] float32."""
    b, h, s, _ = probs.shape
    if v_part.ndim == 4:
        g = v_part.shape[2]
        ctx = jnp.einsum("bgrqk,bkgd->bqgrd",
                         probs.reshape(b, g, h // g, s, -1), v_part,
                         preferred_element_type=jnp.float32,
                         precision=precision)
    else:       # as stored: every head over every lane, its kv head's kept
        g = v_part.shape[2] // hd
        every = jnp.einsum("bnk,bkc->bnc", probs.reshape(b, h * s, -1),
                           v_part, preferred_element_type=jnp.float32,
                           precision=precision)
        own = jnp.eye(g, dtype=bool)[:, None, None, :, None]
        ctx = jnp.sum(jnp.where(
            own, every.reshape(b, g, h // g, s, g, hd), 0), axis=4)
        ctx = jnp.transpose(ctx, (0, 3, 1, 2, 4))
    return ctx.reshape(b, s, h, hd)


def attend(q: jax.Array, k, v, keep, cfg: TransformerConfig,
           precision=None) -> jax.Array:
    """Masked attention of q [B,S,H,Dh] over k/v; `keep` [S, T] marks key
    positions each query may attend to. k, v and keep may each be a tuple
    of parts (a cached step's window and its fresh rows,
    `cache_update_and_read`): one softmax runs over all their keys, and no
    part is copied to sit beside another.

    A part is [B,T,G,Dh], its heads apart, or [B,T,G*Dh], a window in its
    stored form. G divides H (GQA): query head h reads kv head h // (H/G),
    and no part is repeated up to the query heads. A stored window is not
    reshaped (the TPU would copy it whole into a layout with the heads
    apart): its products run over the folded axis against the queries laid
    out in blocks, column (h, s) holding query (s, h) in its kv head's Dh
    lanes and zeros in the others. The zeros add nothing to a sum, and cost
    nothing while the columns fit one MXU pass; a span with more columns
    pays the copy instead and takes the window with its heads apart.

    `precision` is that of the products of two activations: None for the
    narrow ones most families run; a family whose activations and cache are
    float32 says how many bfloat16 passes it needs (one would round both)."""
    b, s, h, hd = q.shape
    k, v = _parts(k), _parts(v)
    if s * h > _MXU_COLUMNS:
        k, v = ([x.reshape(x.shape[:2] + (-1, hd)) for x in parts]
                for parts in (k, v))
    scores = [jnp.where(keep_part[None, None],
                        _scores(q, k_part, precision)
                        / jnp.sqrt(jnp.float32(hd)), -1e30)
              for k_part, keep_part in zip(k, _parts(keep))]
    if len(scores) == 1:
        probs = [jax.nn.softmax(scores[0], axis=-1)]
    else:       # softmax over the parts' concatenation, not concatenated
        top = reduce(jnp.maximum, [jnp.max(part, axis=-1, keepdims=True)
                                   for part in scores])
        probs = [jnp.exp(part - top) for part in scores]
        total = sum(jnp.sum(part, axis=-1, keepdims=True) for part in probs)
        probs = [part / total for part in probs]
    ctx = sum(_context(p_part.astype(q.dtype), v_part, hd, precision)
              for p_part, v_part in zip(probs, v))
    return ctx.astype(q.dtype).reshape(b, s, h * hd)


def attend_width(bcache: LayerCache, read_len: Optional[int]) -> int:
    """Static attend-window width: the full cache, truncated to the
    bucketed `read_len` when one is bound — THE window formula, shared
    by the XLA read path and the Pallas kernel route so they can never
    attend different windows."""
    t_max = next(buf.shape[2] for name, buf in bcache.stack.items()
                 if name != STATS)
    return t_max if read_len is None else min(read_len, t_max)


def cache_write_quantized(bcache: LayerCache, k_new: jax.Array,
                          v_new: jax.Array, width: int) \
        -> Tuple[LayerCache, Cache]:
    """Quantize the new K/V rows (with their per-(position, head)
    scale/shift rows) for writing, and read this layer's int8 window
    [0, width) as a dict of `[B, width, ...]` leaves — the single int8
    write path, shared by the XLA read path and the fused Pallas decode
    kernel. The window is the cache as it was: both readers take the new
    rows from the caller's hands, unquantized."""
    rows = {}
    for t, new in (("k", k_new), ("v", v_new)):
        rows[t], rows[f"{t}_scale"], rows[f"{t}_shift"] = quantize_rows(new)
        rows[t] = _fold(rows[t])
    window = {name: read_window(buf, bcache.layer, width)
              for name, buf in bcache.stack.items()}
    for t in ("k", "v"):    # a scale a head: both readers take the heads apart
        window[t] = window[t].reshape(window[f"{t}_scale"].shape + (-1,))
    return bcache._replace(rows=rows), window

def cache_update_and_read(bcache: LayerCache, k_new: jax.Array,
                          v_new: jax.Array, pos, prefill: bool, s: int,
                          dtype, read_len: Optional[int] = None,
                          window: int = 0, names: tuple = ("k", "v"),
                          ring: bool = False, unread: bool = False):
    """Record the new K/V rows for [pos, pos+S) of this layer and return
    (k, v, keep, cache) for `attend`: k, v and keep are tuples of two
    parts, the cached window [0, width) as it was (one `dynamic_slice` a
    leaf, in its stored form `[B, width, H*Dh]`, kept only below `pos`) and
    the step's own rows (`[B, S, H, Dh]`, causal among themselves). Nothing
    of a whole layer's shape is materialised, and the window is not copied
    to have the rows put into it. A prefill has only
    the second part: it attends its own rows and reads no cache.

    `read_len` (STATIC) truncates the window to cache positions
    [0, read_len): the caller guarantees pos < read_len, and positions
    beyond it were fully masked anyway (their softmax columns are exact
    zeros), so truncation is numerically identical while the attend
    matmul and (for int8 caches) the dequantize shrink from max_len to
    read_len — the bucketed decode-step optimization
    (DecodePipeline::attend_bucket). `window` (STATIC, 0 = off) is a
    sliding attention window: a query at q attends (q - window, q].
    `names` (STATIC) are the two leaves, where a family keeps more than one
    pair. `ring` (STATIC): they are rings (`CacheLeaf.length`, no shorter
    than what of `window` fits `max_len`): the first part is the whole ring
    as stored, whatever `read_len`, each slot kept by the position it holds
    (the largest below `pos` that falls on it, if that is inside the
    query's window). `unread` (STATIC): the first part's k and v are handed
    back as `Window`s, for an attention that reads a head's lanes at a time
    when it comes to them (`read_window`): nothing of the window's whole
    size is then copied out, or live at once."""
    width = attend_width(bcache, None if ring else read_len)
    quantized = "k_scale" in bcache.stack
    if quantized:
        bcache, win = cache_write_quantized(bcache, k_new, v_new, width)
        # the freshly computed rows are in hand — attend over them exactly;
        # quantization error applies only to genuinely cached positions
        k_new, v_new = k_new.astype(dtype), v_new.astype(dtype)
    else:
        stack = bcache.stack
        # through the cache's dtype, as if read back from it
        k_new = k_new.astype(stack[names[0]].dtype).astype(dtype)
        v_new = v_new.astype(stack[names[1]].dtype).astype(dtype)
        bcache = bcache._replace(rows={names[0]: _fold(k_new),
                                       names[1]: _fold(v_new)})
    # query i sits at absolute position pos + i (a prefill has pos 0, the
    # classic decode step s == 1, a SPAN step, the speculative verify,
    # s > 1) and attends every cached row below pos and rows [0, i] of
    # its own step
    q_off = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
    k_off = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    keep_new = k_off <= q_off
    if window:
        keep_new &= k_off > q_off - window
    if prefill:
        return (k_new,), (v_new,), (keep_new,), bcache
    if quantized:   # dequantize only the attended window
        k = dequantize_rows(win["k"], win["k_scale"], win["k_shift"], dtype)
        v = dequantize_rows(win["v"], win["v_scale"], win["v_shift"], dtype)
    else:
        k, v = (Window(stack[name], bcache.layer, width) if unread else
                read_window(stack[name], bcache.layer, width).astype(dtype)
                for name in names)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (s, width), 1)
    if ring:    # the position each slot holds; negative: nothing yet
        k_pos = pos - 1 - jnp.mod(pos - 1 - k_pos, width)
        keep = k_pos >= 0
    else:
        keep = k_pos < pos
    if window:
        q_pos = pos + jax.lax.broadcasted_iota(jnp.int32, (s, width), 0)
        keep &= k_pos > q_pos - window
    return (k, k_new), (v, v_new), (keep, keep_new), bcache


# -- a step whose rows stand each at its own position ----------------------
# The served executor steps every running request's row in one program
# (`parallel/decode_rows.py`): row r is slot `base + r` of a stage-wide cache
# and stands at `pos[r]`, -1 where the slot is dead (free, or not at this
# stage in this tick). Such a step reads slots [base, base + R) of leaves
# that keep a row a position, and no window ladder: it walks the positions
# in blocks up to the furthest live row (`reach`) under one online softmax,
# so one program a count of rows serves every length and every place in the
# cache. A ring (a leaf with a `length`) is walked the same way, slot by
# slot: each slot's ring stands at its own row's position, so slot `s` of
# row r holds the largest `p < pos[r]` with `p mod W == s`, and is masked by
# that; the walk ends at the ring's end, or at `reach` while no row has
# filled its ring.

class RowsAt(NamedTuple):
    """Where the R rows of such a step stand, all traced: the first of
    their slots, each row's position (0 where the row is dead, for reading
    and embedding), the furthest live position, and which rows are live
    (a dead row writes nothing, takes no token and is not counted)."""
    base: jax.Array
    pos: jax.Array
    reach: jax.Array
    live: Optional[jax.Array] = None


def walk_blocks(reach, held: int, block: int):
    """Blocks of `block` positions (no more than the leaf's `held`) that a
    step's walk of a leaf takes with its furthest live row at `reach`."""
    return (jnp.minimum(reach, held) + block - 1) // block


def rows_walked(reach, held: int, block: int):
    """Positions of a leaf of `held` positions that such a walk reads:
    whole blocks, the leaf at most."""
    block = min(block, held)
    return jnp.minimum(walk_blocks(reach, held, block) * block, held)


def attend_rows(bcache: LayerCache, q: jax.Array, k_new: jax.Array,
                v_new: jax.Array, at: RowsAt, block: int,
                cfg: TransformerConfig, window: int = 0,
                names: tuple = ("k", "v"), precision=None,
                ring: bool = False):
    """One decode step's attention for R rows at their own positions:
    q [R,1,H,Dh] and the step's own k_new, v_new [R,1,G,Dh] against slots
    [at.base, at.base + R) of this layer's cache, row r reading the
    positions below `at.pos[r]` (inside its sliding `window`, where one is
    set) and its own new row. -> (context [R,1,H*Dh], the cache with the
    new rows recorded for `write_rows_at`).

    The positions are walked `block` at a time up to `at.reach`, the
    furthest live row's position, each block read as it is stored (`_scores`,
    `_context`) and folded into a running maximum, sum and context: what a
    shorter row does not hold is masked to exact zeros, and nothing past
    it is read. A dead row (position 0) attends its own row alone.

    `ring` (STATIC): the two leaves are rings (`CacheLeaf.length`): a slot is
    kept by the position it holds for ITS row, the largest below
    `at.pos[r]` that falls on it, where that is inside the row's window;
    the walk covers the ring, or the slots below `at.reach` while no row has
    come round."""
    stack = bcache.stack
    rows, _, h, hd = q.shape
    k_buf, v_buf = (stack[name] for name in names)
    held = k_buf.shape[2]
    block = min(block, held)
    # through the cache's dtype, as if read back from it
    k_new = k_new.astype(k_buf.dtype).astype(q.dtype)
    v_new = v_new.astype(v_buf.dtype).astype(q.dtype)
    bcache = bcache._replace(rows={names[0]: _fold(k_new),
                                   names[1]: _fold(v_new)})
    below = at.pos[:, None, None, None]
    scale = jnp.sqrt(jnp.float32(hd))
    top = _scores(q, k_new, precision) / scale              # [R,H,1,1]
    total = jnp.ones_like(top)
    ctx = _context(total.astype(q.dtype), v_new, hd, precision)

    def fold(j, carry):
        top, total, ctx = carry
        # the last block of a cache that is no whole number of blocks
        # starts early, and leaves what the block before it held to it
        first = j * block
        start = jnp.minimum(first, held - block)
        k, v = (jax.lax.dynamic_slice(
            buf, (bcache.layer, at.base, start, 0),
            (1, rows, block, buf.shape[3]))[0].astype(q.dtype)
            for buf in (k_buf, v_buf))
        if ring and rows == 1:
            # one row's ring, its values' heads apart: read as stored, the
            # chip's compiler lays the WHOLE stack of rings out anew for
            # this product, a copy of it every step (402 MB at 32 slots of
            # mellum's six rings: compiled for the described v5e, PR 62;
            # `tests/test_chip_compile_families.py` holds the rung to none)
            v = v.reshape(v.shape[:2] + (-1, hd))
        slot = start + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, 1, block), 3)
        if ring:    # the position each slot holds; negative: nothing yet
            k_pos = below - 1 - jnp.mod(below - 1 - slot, held)
            keep = (slot >= first) & (k_pos >= 0)
        else:
            k_pos = slot
            keep = (slot >= first) & (k_pos < below)
        if window:
            keep &= k_pos > below - window
        part = jnp.where(keep, _scores(q, k, precision) / scale, -1e30)
        new_top = jnp.maximum(top, jnp.max(part, axis=-1, keepdims=True))
        probs = jnp.exp(part - new_top)
        shrink = jnp.exp(top - new_top)
        total = total * shrink + jnp.sum(probs, axis=-1, keepdims=True)
        ctx = ctx * jnp.transpose(shrink, (0, 2, 1, 3)) \
            + _context(probs.astype(q.dtype), v, hd, precision)
        return new_top, total, ctx

    top, total, ctx = jax.lax.fori_loop(
        0, walk_blocks(at.reach, held, block), fold, (top, total, ctx))
    ctx = ctx / jnp.transpose(total, (0, 2, 1, 3))
    return ctx.astype(q.dtype).reshape(rows, 1, h * hd), bcache


def write_rows_at(cache: Cache, rows: Cache, base, pos: jax.Array,
                  rings: tuple = ()) -> Cache:
    """Every layer's new `rows` (leaves `[L, R, 1, ...]`) into the stacked
    cache, row r into slot `base + r` at position `pos[r]` (of a leaf named
    in `rings` at `pos[r] mod W`; the `stats` leaf's counts are added): one
    in-place update a leaf a row, `[L, 1, 1, ...]` at `(0, base + r,
    pos[r])`, as
    `write_rows` makes one for all rows at one position, in a loop over the rows (unrolled, 48
    rows were 290 operations and 0.4 s more of every set-up to lower; a
    step's time is the same: my chip runs, PR 55). A dead row (`pos`
    negative) puts back what its slot held at position 0. Not one scatter a
    leaf: for a scatter the chip's compiler picks a layout of its own for
    the whole stack and copies 2.4 GB of gpt2-medium's 48 slots into it and
    back, twice a leaf a step (`tests/test_chip_compile.py` holds the step
    to no such copy, and the loop's carry to the stack's own layout)."""
    def write(buf, new, ring=False):
        tail = (0,) * (buf.ndim - 3)
        new = new.astype(buf.dtype)

        def one(r, buf):
            where = jnp.maximum(pos[r], 0)
            if ring:
                where = where % buf.shape[2]
            at = (0, base + r, where) + tail
            row = jax.lax.dynamic_slice_in_dim(new, r, 1, axis=1)
            held = jax.lax.dynamic_slice(buf, at, row.shape)
            return jax.lax.dynamic_update_slice(
                buf, jnp.where(pos[r] >= 0, row, held), at)

        return jax.lax.fori_loop(0, new.shape[1], one, buf)

    return {name: buf if not buf.shape[0] else
            add_stats(buf, rows[name]) if name == STATS else
            write(buf, rows[name], name in rings)
            for name, buf in cache.items()}
