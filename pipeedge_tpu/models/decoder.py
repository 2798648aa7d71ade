"""The kit the sparse decoder families share (keye, kimi, qwen3_next, lfm2,
laguna, minicpm_sala, nemotron_h): what a family is NOT is here, so that its module
holds its mixers, its cache's leaves, its kinds of block and its key map,
and imports no other family.
- products with weights stored `[out, in]` (`lin`), in chunks of rows where
  the result is wide (`in_row_chunks`); the dense SwiGLU (`dense_ffn`) and
  the routed expert layer (`routed_experts`); either, as a cached block
  step's FFN, with the counts every family's `STATS` start with (`ffn`);
- a decay's `exp` to an ulp (`exp_ulp`); queries in chunks whose scores stay
  under `SCORE_BYTES` (`query_chunk`, `map_query_chunks`), one softmax over
  masked key parts (`attend_masked`); the hooks of a family on the cached
  decode path alone (`token_hooks`);
- loading: leaves stacked a run as records of their parts, made and placed
  one at a time (`stack`, `on_device`, `assemble_shard`), and the loaders
  (`loader`) of a family's `_assemble(cfg, shard_config, get, dtype)`.
It imports `layers`, `shard`, `ops/masked_attention.py`, `parallel/expert.py`
(which imports `layers`); a block step's cache is `models/stage_cache.py`.
"""
from __future__ import annotations

import mmap
from typing import Callable, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig
from .. import telemetry
from ..ops import masked_attention
from ..parallel.expert import ACTS, topk_ffn_delta
from .layers import TransformerConfig, exact_dot
from .shard import build_shard_params

# bytes of float32 attention scores one chunk of queries may hold, and of
# the three-pass result of one chunk of rows of a wide product
SCORE_BYTES = 1 << 29
PRODUCT_BYTES = 1 << 29

# what every family's block step counts first into the cache's `stats`
# leaf, in this order (`ffn`); a family's own counts follow
MOE_STATS = ("moe_assignments", "moe_rows_computed", "moe_experts_touched",
             "moe_grouped_calls", "moe_layer_calls")


def lin(w: jax.Array, x: jax.Array) -> jax.Array:
    """x over w stored [out, in] (`exact_dot`)."""
    return exact_dot(x, w, w_contract=1).astype(x.dtype)


def in_row_chunks(fn, x: jax.Array, widest: int) -> jax.Array:
    """`fn` over the rows of x [B, S, D] -> [B, S, N], in chunks of rows
    whose three-pass product of width `widest` stays under
    `PRODUCT_BYTES`."""
    b, s, d = x.shape
    rows, n = b * s, 1
    while rows % (2 * n) == 0 and rows // n * widest * 12 > PRODUCT_BYTES:
        n *= 2
    if n == 1:
        return fn(x)
    out = jax.lax.map(fn, x.reshape(n, 1, rows // n, d))
    return out.reshape(b, s, -1)


def dense_ffn(p: Dict, normed: jax.Array, act: str = "silu") -> jax.Array:
    """A dense FFN of `normed` [B, S, D] in chunks of rows: a SwiGLU where
    `p` has a gate matrix, else `down(act(up x))` (`expert.ACTS`)."""
    def mlp(rows):
        if "gate" in p:
            hidden = jax.nn.silu(lin(p["gate"], rows)) * lin(p["up"], rows)
        else:
            hidden = ACTS[act](lin(p["up"], rows))
        return lin(p["down"], hidden)
    return in_row_chunks(mlp, normed, p["up"].shape[0])


def exp_ulp(x: jax.Array) -> jax.Array:
    """exp(x) for float32 x <= 0 to an ulp: x = n ln 2 + r (ln 2 in two
    parts), a polynomial in r (Cephes `expf`'s), 2**n from its bits.

    A decay is applied a position after another (a chunk after another), so
    its error compounds over a head's memory, thousands of positions for the
    slowest. The chip's own float32 `exp` is a few 1e-7 off WITH A BIAS: the
    one-token form lay 1.7e-4 from a float64 recurrence after 512 positions
    where the chunked form, which takes the exp of sums, lay 3e-6, and the
    plain reference's scan over 32 k positions 1e-3 of the logits' range
    from the program (my chip runs, PR 33). `1 + expm1(x)` repaired the
    slowest heads only (the chip's `expm1` is `exp - 1` but for tiny x)."""
    n = jnp.round(x * 1.44269504088896341)
    r = (x - n * 0.693359375) - n * -2.12194440e-4
    poly = 1.9875691500e-4
    for c in (1.3981999507e-3, 8.3334519073e-3, 4.1665795894e-2,
              1.6666665459e-1, 5.0000001201e-1):
        poly = poly * r + c
    two_n = jax.lax.bitcast_convert_type(
        (jnp.maximum(n, -126.0).astype(jnp.int32) + 127) << 23, jnp.float32)
    return jnp.where(x < -87.0, 0.0, (poly * r * r + r + 1.0) * two_n)


def by_head(x: jax.Array, heads: int) -> tuple:
    """[B, K, heads * Dh] -> one [B, K, Dh] a head."""
    return tuple(jnp.split(x, heads, axis=-1))


def routed_experts(p: Dict, normed, cfg: TransformerConfig, live=None):
    """The routed FFN's delta (with the shared expert's, where the block
    hands one over; through the block's `latent`, where it has one) and
    counts: `p["experts"]` is the block's own leaves, or
    `(stack, layer)` where the decode scan keeps the stacked blocks'
    experts whole (the decode driver's `_run_blocks`). `live`: which of the
    tokens count (`topk_ffn_delta`); None = all."""
    experts, layer = p["experts"], None
    if isinstance(experts, tuple):
        experts, layer = experts
    return topk_ffn_delta(
        dict({name: p[name] for name in ("router", "shared", "shared_gate",
                                         "latent")
              if name in p}, experts=experts), normed, cfg, layer=layer,
        live=live)


def ffn(p: Dict, normed: jax.Array, cfg: TransformerConfig, live=None):
    """The FFN of a cached block step over `normed`, its input's second
    norm: the routed experts where the block has a router, else its dense
    SwiGLU. `live` (bool, a token of `normed` each; None = all): the rows of
    a step that stand for a request; the others go to no expert and are not
    counted. -> (delta, what the call counts under `MOE_STATS`, int32 [5])."""
    if "router" in p:
        delta, moe = routed_experts(p, normed, cfg, live)
        return delta, jnp.concatenate([moe.astype(jnp.int32),
                                       jnp.ones(1, jnp.int32)])
    return dense_ffn(p["mlp"], normed), jnp.zeros(5, jnp.int32)


def query_chunk(n_q: int, scores_per_query_bytes: int) -> int:
    """The most of `n_q` queries, halved while even, whose float32 scores
    (`scores_per_query_bytes` a query: rows x heads live at once x keys x 4)
    stay under `SCORE_BYTES`."""
    chunk = n_q
    while chunk > 1 and chunk % 2 == 0 \
            and chunk * scores_per_query_bytes > SCORE_BYTES:
        chunk //= 2
    return chunk


def split_queries(x: jax.Array, chunk: int) -> jax.Array:
    """[B, Q, ...] -> [Q / chunk, B, chunk, ...], what `lax.map` walks."""
    b, n_q = x.shape[:2]
    return jnp.moveaxis(
        x.reshape((b, n_q // chunk, chunk) + x.shape[2:]), 1, 0)


def join_queries(x: jax.Array) -> jax.Array:
    """The way back: [n, B, chunk, ...] -> [B, n * chunk, ...]."""
    n, b, chunk = x.shape[:3]
    return jnp.moveaxis(x, 0, 1).reshape((b, n * chunk) + x.shape[3:])


def map_query_chunks(fn, chunk: int, queries: tuple, rows: tuple = ()):
    """`fn(queries, rows)` a chunk of `chunk` queries after another
    (`query_chunk`; one call where that is all of them): `queries` arrays
    [B, Q, ...], handed as [B, chunk, ...]; `rows` arrays [Q, ...] (a mask's
    rows, the queries' positions), handed as [chunk, ...]. `fn` returns its
    queries' rows [B, chunk, ...], or a tuple of them and counts, which are
    summed over the chunks. -> [B, Q, ...] (and the counts)."""
    n_q = queries[0].shape[1]
    if chunk == n_q:
        return fn(queries, rows)
    out = jax.lax.map(lambda xs: fn(*xs), (
        tuple(split_queries(x, chunk) for x in queries),
        tuple(x.reshape((n_q // chunk, chunk) + x.shape[1:]) for x in rows)))
    if isinstance(out, tuple):
        return (join_queries(out[0]),) \
            + tuple(jnp.sum(count) for count in out[1:])
    return join_queries(out)


# -- one softmax over selection-masked key parts -------------------------------

# rows of a tile (queries x a KV group's query heads) from which a span's
# masked attention takes the streaming kernel: one full tile of the matrix
# unit's 128 rows. Under it a call is a decode step's (one query a row of the
# batch: 8 or 16 rows a group), bound by the bytes of its keys, which the
# einsums read once as well. The cells' spans have 512 to 4,096 rows
FUSED_ROWS = 128

# what a family's block step counts after its own counts where it attends
# through `attend_masked`: calls whose attention took the kernel
ATTEND_STATS = ("attend_fused_calls",)


def _fused_mode():
    """How this backend runs the masked-attention kernel: "mosaic" on a TPU,
    None where Mosaic cannot run (the einsums serve every call); the tests
    put "interpret" here."""
    return "mosaic" if jax.default_backend() == "tpu" else None


def softmax_over(q, ks, vs, keeps, spec: str):
    """One softmax over key parts: scores `spec`(q, k) a part at `HIGHEST`,
    over the root of the head's width, masked by `keeps` (-1e30: a masked
    key's weight is exactly 0); the weights are divided by their sum after
    they have met the values. `spec` names q's and a part's axes, e.g.
    "bqrd,bkd->brqk". -> (the weighted values, q's axes; the weights' sum,
    the scores' axes but the keys')."""
    hd = q.shape[-1]

    def dots(spec, x, y):
        return jnp.einsum(spec, x, y, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    scores = [jnp.where(keep, dots(spec, q, k) * hd ** -0.5, -1e30)
              for k, keep in zip(ks, keeps)]
    top = jnp.max(jnp.concatenate(
        [jnp.max(sc, axis=-1, keepdims=True) for sc in scores], -1),
        axis=-1, keepdims=True)
    probs = [jnp.exp(sc - top) for sc in scores]
    total = sum(jnp.sum(pr, axis=-1) for pr in probs)
    ins, out = spec.split("->")
    back = f"{out},{ins.split(',')[1]}->{ins.split(',')[0]}"
    mixed = sum(dots(back, pr, v) for pr, v in zip(probs, vs))
    return mixed, total


def attend_masked(q, ks, vs, keeps):
    """A KV group's query heads q [B, Q, r, Dh] over key parts ks, vs (a
    [B, K, Dh] a part, as read) under the masks `keeps` (a bool [B or 1, Q,
    K] a part; every query keeps a key of some part): one softmax over all
    parts. -> (context [B, Q, r, Dh] float32, 1 where the streaming kernel
    ran, else 0).

    Which way is read off the call (`kernel_mode`, which a caller that
    sizes its query chunks for the einsums' scores asks as well): float32
    operands, heads of whole lanes, parts of whole key blocks, `FUSED_ROWS`
    rows or more, on a backend that runs Mosaic. The kernel holds no score
    outside VMEM: a call that takes it needs no chunks of queries (keye and
    minicpm_sala chunk for their selections; qwen3_next hands a span whole)."""
    b, n_q, heads, hd = q.shape
    mode = kernel_mode(n_q * heads, hd, q.dtype, [k.shape[1] for k in ks])
    if not mode:
        mixed, total = softmax_over(
            q, ks, vs, [keep[:, None] for keep in keeps], "bqrd,bkd->brqk")
        return mixed / jnp.moveaxis(total, 1, 2)[..., None], 0
    ctx = masked_attention.attend(
        jnp.moveaxis(q, 2, 1), [k.astype(q.dtype) for k in ks],
        [v.astype(q.dtype) for v in vs],
        [jnp.broadcast_to(keep, (b, n_q, k.shape[1]))
         for k, keep in zip(ks, keeps)], interpret=mode == "interpret")
    return jnp.moveaxis(ctx, 1, 2), 1


def kernel_mode(rows: int, head_dim: int, dtype, part_keys):
    """How `attend_masked` runs a call of `rows` rows (queries x the KV
    group's query heads) with heads of `head_dim` lanes in `dtype` over
    parts of `part_keys` keys each: `_fused_mode()` where the streaming
    kernel takes it, None where the einsums do."""
    fits = rows >= FUSED_ROWS and head_dim % 128 == 0 \
        and dtype == jnp.float32 \
        and all(masked_attention.key_block(n) for n in part_keys)
    return _fused_mode() if fits else None


def token_hooks(name: str, dtype, norm: Callable) -> Dict:
    """`FamilySpec`'s hooks of a family whose embedding is its tokens' rows
    in `dtype` (positions live in its mixers), that runs through the cached
    decode path only, and whose head follows `norm(p, x, eps)`. Where the
    embedding's or the head's leaves hold a `factor` (a family's loader puts
    it there from the configuration: MiniCPM's `scale_emb`, and its
    `dim_model_base / hidden_size`), the rows, or the head's normed input,
    are multiplied by it."""
    def span_embed(pe: Dict, tok: jax.Array, pos) -> jax.Array:
        """Token embedding [B, K] -> [B, K, D]."""
        rows = jnp.take(pe["wte"], tok, axis=0).astype(dtype)
        return rows * pe["factor"].astype(dtype) if "factor" in pe else rows

    def embed(p: Dict, input_ids: jax.Array, cfg: TransformerConfig):
        return span_embed(p, input_ids, 0)

    def decode_embed(pe: Dict, tok: jax.Array, pos) -> jax.Array:
        return span_embed(pe, tok.reshape(-1, 1), pos)

    def sublayer(p: Dict, sub: int, data, cfg: TransformerConfig,
                 attention_fn=None):
        raise NotImplementedError(
            f"the {name} family runs through the cached decode path only: "
            "its blocks come in runs of more than one kind, which the "
            "forward path (models/shard.py shard_apply) does not scan yet")

    def finalize(p: Dict, hidden: jax.Array, cfg: TransformerConfig):
        """Final norm + LM head -> [B, S, vocab] logits."""
        normed = norm(p["ln"], hidden, cfg.layer_norm_eps)
        if "factor" in p:
            normed = normed * p["factor"].astype(normed.dtype)
        return lin(p["head"]["w"], normed)

    return dict(embed=embed, span_embed=span_embed, decode_embed=decode_embed,
                sublayer=sublayer, finalize=finalize)


# -- loading -------------------------------------------------------------------

class Stacked:
    """A `[n, ...]` host leaf that is not made yet: its parts, each a host
    array or a `Stacked` itself (a layer's experts, then the run's blocks),
    and the shape and dtype `np.stack` would give them. `on_device` makes
    it, once, where it is placed; a family that computes on one on the host
    gets an array from `np.asarray` (`__array__`)."""

    __slots__ = ("parts", "shape", "dtype", "nbytes")

    def __init__(self, parts):
        self.parts = tuple(parts)
        self.shape = (len(self.parts),) + tuple(self.parts[0].shape)
        if any(tuple(part.shape) != self.shape[1:] for part in self.parts):
            raise ValueError("all parts of a stack must have the same shape")
        self.dtype = np.result_type(*(part.dtype for part in self.parts))
        self.nbytes = self.dtype.itemsize * int(
            np.prod(self.shape, dtype=np.int64))

    def __array__(self, dtype=None, copy=None):
        out = np.empty(self.shape, dtype or self.dtype)
        _fill(out, self)
        return out


def _mapped(leaf: np.ndarray) -> bool:
    """Whether a host array is a view of a mapped file (`registry.
    _TimedReads`): its memory is an `mmap`'s, and copying it is the read."""
    while isinstance(leaf, np.ndarray):
        leaf = leaf.base
    return isinstance(leaf, mmap.mmap)


def _fill(out: np.ndarray, leaf) -> None:
    """Copy a host leaf into `out`, part by part into its slot."""
    if isinstance(leaf, Stacked):
        for slot, part in enumerate(leaf.parts):
            _fill(out[slot, ...], part)
    else:
        np.copyto(out, leaf)


def _memory_order(leaf: np.ndarray) -> tuple:
    """The axes of a host array from the one its memory steps slowest along
    to the fastest: `range(ndim)` for a C-ordered array, (1, 0) for the
    `.T` of one."""
    return tuple(sorted(range(leaf.ndim),
                        key=lambda axis: -abs(leaf.strides[axis])))


class _Rooms:
    """Two host buffers in which `on_device` makes its leaves by turns:
    leaf n + 1 is made in the one while leaf n's transfer reads the other,
    and leaf n + 2 takes leaf n's, which by then has been waited for.
    Memory that was touched once stays mapped; a leaf made in memory fresh
    from `np.empty` pays a page fault every 4 KiB on top of its copy. A
    backend that kept a leaf's room as the leaf's own buffer (XLA's CPU
    client does that to a host array on a 64-byte boundary, where the cast
    changes nothing) keeps it: the next leaf of that turn gets a new one."""

    def __init__(self):
        self._rooms, self._leaves, self._turn = [None, None], [None, None], 0

    def take(self, nbytes: int) -> np.ndarray:
        """`nbytes` of the other room than the last leaf's."""
        turn = self._turn = 1 - self._turn
        room, leaf = self._rooms[turn], self._leaves[turn]
        if room is None or room.nbytes < nbytes or self._kept(room, leaf):
            other = self._rooms[1 - turn]
            room = self._rooms[turn] = np.empty(
                max(nbytes, 0 if other is None else other.nbytes), np.uint8)
        return room[:nbytes]

    def placed(self, leaf) -> None:
        """`leaf` is what the device made of the room taken last."""
        self._leaves[self._turn] = leaf

    @staticmethod
    def _kept(room: np.ndarray, leaf) -> bool:
        try:
            at = leaf.unsafe_buffer_pointer() - room.ctypes.data
        except (AttributeError, TypeError, ValueError):    # a traced leaf
            return True
        return 0 <= at < room.nbytes


def _host(leaf, rooms: _Rooms):
    """A leaf as `_put` should be handed it: a `Stacked` or a view of the
    weights file as ONE new array in a room of `rooms`, each byte copied
    once, from where it lies and in the order it lies there (a kernel its
    family turned `.T` fills an array that is the `.T` of a C-ordered one:
    a copy between arrays whose strides differ runs at a fifth of one
    between like ones); anything else (an array a family computed, a drawn
    one, a traced value) as it is. Where the parts are views of the file
    (the first says) the copy is the file's read, a `weights_read` span a
    leaf."""
    first = leaf
    while isinstance(first, Stacked):
        first = first.parts[0]
    from_file = isinstance(first, np.ndarray) and _mapped(first)
    if not isinstance(leaf, Stacked) and not from_file:
        return leaf
    lead = len(leaf.shape) - first.ndim
    order = tuple(range(lead)) + tuple(
        lead + axis for axis in _memory_order(first))
    out = rooms.take(leaf.nbytes).view(leaf.dtype).reshape(
        [leaf.shape[axis] for axis in order]).transpose(np.argsort(order))
    if from_file:
        with telemetry.startup("weights_read"):
            _fill(out, leaf)
    else:
        _fill(out, leaf)
    return out


def _put(host) -> jax.Array:
    """`jnp.asarray(host)`, but a host array whose memory runs in another
    order than its axes goes over as it lies and is turned on the device,
    where that is a pass at the HBM's speed: `jnp.asarray` would make it
    C-ordered on the host first (keye's head, 622 MB, 5.2 s of a 20 s load:
    `tools/bench_loader.py`, my chip run, PR 53)."""
    if isinstance(host, np.ndarray) and not host.flags.c_contiguous:
        order = _memory_order(host)
        as_it_lies = host.transpose(order)
        if as_it_lies.flags.c_contiguous:
            return jnp.asarray(as_it_lies).transpose(np.argsort(order))
    return jnp.asarray(host)


def stack(leaves):
    """One `[n, ...]` leaf of like leaves: of host arrays (and of stacks of
    them) the record of its parts, `Stacked`, which `on_device` makes once
    (the host never holds a layer twice, nor the device); of anything else
    `jnp.stack`'s array."""
    if isinstance(leaves[0], (np.ndarray, Stacked)):
        return Stacked(leaves)
    return jnp.stack(leaves)


def on_device(params, dtype, float32: tuple = ()):
    """Host leaves onto the device in `dtype`, one at a time, each made on
    the host (`_host`) just before. The fence trails by one leaf: leaf n's
    transfer and cast are started, leaf n + 1 is made on the host
    meanwhile, and only then is leaf n waited for, so the link and the
    chip's cast work while the host copies. A fence there must be:
    transfers are asynchronous, and unfenced every leaf's float16 copy from
    the file would sit on the device beside the converted model (16.8 GB of
    a 16.9 GB chip, my chip run, PR 27); trailing by one, one leaf's
    float16 copy is there at a time (twice while a turned leaf is turned,
    `_put`), and the last leaf is waited for before this returns.
    `float32`: the leaves the family keeps in float32 as published (a
    router's correction bias), each the last keys of its path."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    out, rooms = [], _Rooms()
    for path, leaf in flat:
        keys = tuple(getattr(step, "key", None) for step in path)
        keep = any(keys[-len(last):] == last for last in float32)
        host = _host(leaf, rooms)
        jax.block_until_ready(out[-1:])
        out.append(_put(host).astype(jnp.float32 if keep else dtype))
        if host is not leaf:
            rooms.placed(out[-1])
    jax.block_until_ready(out[-1:])
    return jax.tree_util.tree_unflatten(tree, out)


def assemble_shard(shard_config: ShardConfig, get_embed, get_block,
                   get_final, dtype, kind=None, float32: tuple = ()) -> Dict:
    """`build_shard_params` of getters whose leaves are host arrays, views
    of the mapped weights file among them: every leaf stays what the getter
    gave until its run of like blocks (`kind(block_id)`) is stacked, which
    copies nothing (`stack`: a `Stacked` of the blocks' leaves, themselves
    `Stacked` where a layer's experts are), then is made and goes to the
    device (`on_device`). Traced values pass through as well:
    `jax.eval_shape` over a family's `_assemble` with a `get` of
    `jnp.zeros` gives a model's shapes without its values."""
    return on_device(build_shard_params(
        shard_config, get_embed, get_block, get_final,
        stack=lambda blocks: jax.tree_util.tree_map(
            lambda *leaves: stack(leaves), *blocks),
        kind=kind), dtype, float32)


def whole_blocks(name: str, subs: tuple) -> None:
    """Refuse the sublayers `subs` of a block unless they are all four."""
    if subs != (0, 1, 2, 3):
        raise NotImplementedError(
            f"the {name} family takes whole blocks: a partition that cuts "
            "one is for the forward path, which it does not run")


def norm_ones(key: str, shape: tuple):
    """The rule of what `init_params` does not draw: a norm's weight is 1."""
    if key.endswith("norm.weight"):
        return np.ones(shape, np.float32)
    return None


def loader(assemble: Callable, undrawn: Callable = norm_ones,
           vocabulary: tuple = ("model.embed_tokens.weight",
                                "lm_head.weight")) \
        -> Tuple[Callable, Callable]:
    """(load_params, init_params) of a family from its `assemble(cfg,
    shard_config, get, dtype)`, where `get(key, shape)` is a tensor of the
    published scheme: read from a state-dict npz and checked against the
    model's shape (a sliced vocabulary is the first rows of the tables the
    scheme names `vocabulary`), or drawn from `seed`, but for the keys
    `undrawn(key, shape)` gives a value for. One assembly, so the two trees
    agree leaf by leaf."""
    def load_params(cfg: TransformerConfig, shard_config: ShardConfig,
                    weights: Mapping, dtype=jnp.float32) -> Dict:
        """Shard params from a published-style state-dict npz."""
        def get(key, shape):
            value = np.asarray(weights[key])
            if key in vocabulary:
                value = value[:shape[0]]
            if value.shape != shape:
                raise ValueError(f"{key}: {value.shape} in the file, {shape} "
                                 "in the model")
            return value
        return assemble(cfg, shard_config, get, dtype)

    def init_params(cfg: TransformerConfig, shard_config: ShardConfig,
                    seed: int = 0, dtype=jnp.float32) -> Dict:
        """Random shard params with `load_params`' pytree structure."""
        rng = np.random.default_rng(seed)

        def get(key, shape):
            value = undrawn(key, shape)
            if value is None:
                value = rng.normal(0, 0.02, size=shape).astype(np.float32)
            return value
        return assemble(cfg, shard_config, get, dtype)

    return load_params, init_params
