"""Grouped expert FFN: rows sorted by group, each group through its own
expert of a stack (`down(silu(gate x) * up x)`, or `down(act(up x))` of an
expert without a gate matrix), the groups walked inside ONE Pallas kernel.

The decode step's expert layer (`parallel/expert.py::topk_ffn_delta`) is
bound by the bytes of the experts it touches. A loop over groups pays, for
every touched expert, a trip, three slices of the stack, a gather of rows
and a write; here the work list lives in SMEM (scalar prefetch), the block
index maps read it, and Pallas' pipeline streams one expert's matrices
while the one before is multiplied. An empty group is never on the list, so
its matrices are never read; the stacks are indexed where they lie
(`[G, out, in]`, any leading axes flattened by the caller: a free reshape),
never copied. Up, activation and down are one walk: the hidden rows of a
visit live in VMEM and nowhere else, and nothing outside the kernel touches
a row that no visit owns (what a hidden in HBM and a lay-out of the parts
by XLA cost: PERF.md, PR 50).

Layout. Rows lie in the order of their groups, cut into row tiles of
`row_tile`; where a group starts is the caller's to say (packed one after
another, or each on a tile's first row). A work item is one (row tile,
group) pair that share rows: a tile that straddles three groups is visited
three times, each visit puts the whole tile through one group's expert and
stores only that group's rows (the output block stays in VMEM between
consecutive visits of one tile, as
`jax.experimental.pallas.ops.tpu.megablox.gmm` does it). Items are ordered
by tile, so consecutive items of one group reuse the expert's blocks
without a second read (where the hidden is cut in blocks, `hidden_block`,
a group's second tile walks them again: kimi's shapes alone, and there a
group passes its tile in one call of some hundreds). A visit costs the
matrix unit about what the blocks' bytes cost the HBM (PERF.md, PR 41), so
a second visit to a group is not free: groups near a tile's size want to
start on a tile's first row. Rows that belong to no group are never
written: callers select, they do not multiply by zero.

Grid `(items, hidden blocks)`: a step takes the item's row tile `[row_tile,
K]` as it is, the `up` (and `gate`) rows and the `down` columns of one
block of the hidden, and adds its part of `down`'s product to the output
block `[row_tile, D]`, which stays where it is over the item's blocks. One
block where an expert's matrices fit `BLOCK_BYTES` (five of the six cells):
the sums are then `exact_dot`'s, product for product; where the hidden is
cut, `down`'s float32 partial sums are added a block at a time.

Parts: float32 rows over bfloat16 matrices are split in VMEM into
`layers.exact_dot`'s three bfloat16 parts (`row_parts`), the parts of a row
tile next to each other, so one product a matrix covers all three and a
matrix is read once, not three times; the kernel adds the three results in
float32, the same sums as `exact_dot`'s in the same order. The hidden is
split the same way where it is made.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of the blocks one grid step streams (an expert's rows of `up` and
# `gate` and columns of `down` for one block of the hidden; two such sets in
# flight): the widest block of the hidden under this. A whole expert of five
# of the six cells (lfm2's three matrices are 22.0 MB); kimi's 88 MB go in
# four (tools/bench_expert_layer.py --block-mib; PERF.md, PR 50)
BLOCK_BYTES = 24 << 20

# what the kernel may take of VMEM beside the compiler's own (a v5e core has
# 128 MiB; the default scope of 16 MiB does not hold two operands' blocks)
_VMEM_LIMIT = 96 << 20


class Items(NamedTuple):
    """The kernel's work list, int32 [W] each but `count` []: item i is the
    visit of row tile `tile[i]` to group `group[i]` (an index into the
    flattened stack), which owns rows `[start[i], end[i])`."""
    group: jax.Array
    tile: jax.Array
    start: jax.Array
    end: jax.Array
    count: jax.Array


def max_items(rows: int, groups: int, row_tile: int) -> int:
    """Most items `rows` sorted rows in `groups` groups can make: every
    tile once, and once more for each group that starts inside one."""
    return -(-rows // row_tile) + min(groups, rows)


def pick(table: jax.Array, index: jax.Array) -> jax.Array:
    """`table[index]` of a short vector, as one comparison a pair and a sum.
    The chip gathers single values one after another (35 ns each: 1,536 of
    them from three tables were 160 us of a 1.2 ms layer call; PERF.md,
    PR 41), while this is one pass of the vector unit."""
    flat = index.reshape(-1)
    hit = flat[:, None] == jnp.arange(table.shape[0])[None, :]
    return jnp.sum(jnp.where(hit, table[None, :], 0),
                   axis=1).reshape(index.shape)


def count_up_to(ascending: jax.Array, values: jax.Array) -> jax.Array:
    """For each of `values`, how many entries of the short vector
    `ascending` are at most it (`searchsorted(..., side="right")` as one
    comparison a pair, for the reason `pick` gives)."""
    flat = values.reshape(-1)
    return jnp.sum(ascending[None, :] <= flat[:, None],
                   axis=1).reshape(values.shape)


def group_items(starts: jax.Array, ends: jax.Array, first_group,
                row_tile: int, n_items: int) -> Items:
    """The work list of groups whose rows are `[starts[g], ends[g])` (int
    [groups], ascending, no two groups sharing a row; an empty group has
    `ends[g] == starts[g]`), group g being matrix `first_group + g` of the
    stack (`first_group` possibly traced), padded to `n_items`
    (`max_items`). At least one item, so that the kernel's grid is never
    empty: where no group has a row it owns no row."""
    first_tile = starts // row_tile
    tiles_of = jnp.where(ends > starts,
                         (ends - 1) // row_tile - first_tile + 1, 0)
    item_ends = jnp.cumsum(tiles_of)
    item = jnp.arange(n_items)
    group = jnp.minimum(count_up_to(item_ends, item), starts.shape[0] - 1)
    # item i is tile `first_tile + i - (items before its group)` of its group
    tile = item + pick(first_tile - item_ends + tiles_of, group)
    used = item < item_ends[-1]
    as_int = functools.partial(jnp.asarray, dtype=jnp.int32)
    return Items(group=as_int(first_group + group),
                 tile=as_int(jnp.where(used, tile, 0)),
                 start=as_int(jnp.where(used, pick(starts, group), 0)),
                 end=as_int(jnp.where(used, pick(ends, group), 0)),
                 count=as_int(jnp.maximum(item_ends[-1], 1)))


def column_block(n: int, k: int, itemsize: int) -> int:
    """Of the `n` columns of an expert's hidden, how many one block holds
    where a column is `k` values of the matrices (its row of `up` and of
    `gate`, its column of `down`): the largest multiple of 128 that divides
    `n` and keeps the block under `BLOCK_BYTES`; all of `n` where it is no
    multiple of 128 (a whole axis is always a legal block)."""
    if n % 128:
        return n
    lanes = n // 128
    fit = max(1, BLOCK_BYTES // (128 * k * itemsize))
    return 128 * max(d for d in range(1, lanes + 1)
                     if lanes % d == 0 and d <= fit)


def row_parts(x: jax.Array, dtype):
    """(parts, `x` as rows of `dtype` for a product with a matrix of
    `dtype`, the parts one after another along the rows): `exact_dot`'s
    three cases. Float32 rows over a narrower matrix are the three parts
    `layers._three_parts` makes, value for value: each the remainder
    rounded to the nearest bfloat16, ties to even, the remainder then less
    that part. The rounding is done on the bits (add half of the last kept
    place, less one where that place is even, and drop what is below it),
    not by a cast there and back, whose excess precision a compiler may
    keep (`_three_parts`' docstring), and Mosaic has no
    `reduce_precision`: what comes out of integer arithmetic has no
    excess. Not for a NaN, which this may round to an infinity."""
    if x.dtype != jnp.float32 or dtype == jnp.float32:
        return 1, x.astype(dtype)
    parts, rest = [], x
    for _ in range(3):
        bits = jax.lax.bitcast_convert_type(rest, jnp.uint32)
        even = (bits >> 16) & jnp.uint32(1)
        kept = (bits + jnp.uint32(0x7FFF) + even) & jnp.uint32(0xFFFF0000)
        part = jax.lax.bitcast_convert_type(kept, jnp.float32)
        parts.append(part.astype(dtype))
        rest = rest - part
    return 3, jnp.concatenate(parts, axis=0)


def _kernel(group_ref, tile_ref, start_ref, end_ref, x_ref, *refs,
            row_tile: int, blocks: int, act, hidden_dtype, precision):
    del group_ref
    *up_refs, down_ref, o_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)

    def product(parts, rows, w_ref):
        whole = jax.lax.dot_general(
            rows, w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        out = whole[:row_tile]
        for part in range(1, parts):
            out = out + whole[part * row_tile:(part + 1) * row_tile]
        return out

    x = row_parts(x_ref[...], down_ref.dtype)       # [parts * row_tile, K]
    hidden = product(*x, up_refs[-1])
    if len(up_refs) == 2:
        hidden = jax.nn.silu(product(*x, up_refs[0])) * hidden
    else:
        hidden = act(hidden)
    hidden = row_parts(hidden.astype(hidden_dtype), down_ref.dtype)
    y = product(*hidden, down_ref)                  # [row_tile, D]
    before = o_ref[...]
    if blocks > 1:      # this block's part of `down`'s sums
        y = jnp.where(j == 0, y, before + y)
    row = tile_ref[i] * row_tile + jax.lax.broadcasted_iota(
        jnp.int32, y.shape, 0)
    owned = (row >= start_ref[i]) & (row < end_ref[i])
    o_ref[...] = jnp.where(owned, y, before)


def grouped_ffn(x: jax.Array, weights: Sequence[jax.Array], items: Items,
                *, row_tile: int, act=None,
                interpret: bool = False) -> jax.Array:
    """Rows through their groups' experts -> float32 `[tiles * row_tile, D]`.

    `x` `[tiles * row_tile, K]`: the sorted rows as they were gathered,
    float32 or the matrices' dtype. `weights`: the stacks `(up [G, F, K],
    down [G, D, F])`, `nn.Linear` layout as stored, and the result is
    `down(act(up x))`; or `(gate, up, down)`, and it is `down(silu(gate x) *
    up x)`. The arithmetic is `exact_dot`'s by the two dtypes (`row_parts`;
    both float32: `HIGHEST`), the hidden cast to `x`'s dtype between the
    products as `expert._expert_ffn` casts it. Rows no item owns are
    whatever the buffer held."""
    *ups, down = weights
    (f, k), d = ups[0].shape[1:], down.shape[1]
    tiles = x.shape[0] // row_tile
    tf = column_block(f, len(ups) * k + d, down.dtype.itemsize)
    precision = None
    if x.dtype == down.dtype == jnp.float32:
        precision = jax.lax.Precision.HIGHEST

    def up_index(i, j, group, tile, start, end):
        return group[i], j, 0

    def down_index(i, j, group, tile, start, end):
        return group[i], 0, j

    def row_index(i, j, group, tile, start, end):
        return tile[i], 0

    return pl.pallas_call(
        functools.partial(_kernel, row_tile=row_tile, blocks=f // tf,
                          act=act, hidden_dtype=x.dtype,
                          precision=precision),
        out_shape=jax.ShapeDtypeStruct((tiles * row_tile, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((row_tile, k), row_index)]
            + [pl.BlockSpec((None, tf, k), up_index)] * len(ups)
            + [pl.BlockSpec((None, d, tf), down_index)],
            out_specs=pl.BlockSpec((row_tile, d), row_index),
            grid=(items.count, f // tf)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="grouped_ffn",
    )(items.group, items.tile, items.start, items.end, x, *weights)
