"""Grouped matmul: rows sorted by group, each group times its own matrix of
a stack, the groups walked inside ONE Pallas kernel.

The decode step's expert layer (`parallel/expert.py::topk_ffn_delta`) is
bound by the bytes of the experts it touches. A loop over groups pays, for
every touched expert, a trip, three slices of the stack, a gather of rows
and a write; here the work list lives in SMEM (scalar prefetch), the block
index maps read it, and Pallas' pipeline streams one expert's matrix while
the one before is multiplied. An empty group is never on the list, so its
matrix is never read; the stack is indexed where it lies (`[G, N, K]`, any
leading axes flattened by the caller: a free reshape), never copied.

Layout. Rows lie in the order of their groups, cut into row tiles of
`row_tile`; where a group starts is the caller's to say (packed one after
another, or each on a tile's first row). A work item is one (row tile,
group) pair that share rows: a tile that straddles three groups is visited
three times, each visit multiplies the whole tile by one group's matrix and
stores only that group's rows (the output block stays in VMEM between
consecutive visits of one tile, as
`jax.experimental.pallas.ops.tpu.megablox.gmm` does it). Items are ordered
by tile, so consecutive items of one group reuse the matrix block without a
second read. A visit costs the matrix unit about what the block's bytes
cost the HBM (PERF.md, PR 41), so a second visit to a group is not free:
groups near a tile's size want to start on a tile's first row. Rows that
belong to no group are never written: callers select, they do not multiply
by zero.

`parts`: float32 rows over bfloat16 matrices arrive as `layers.exact_dot`'s
three bfloat16 parts, the parts of a row tile next to each other (`[tiles,
parts, row_tile, K]` flattened), so one product a visit covers all three
and a matrix is read once, not three times; the kernel adds the three
results in float32, the same sums as `exact_dot`'s in the same order.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one matrix block the pipeline streams (two of them in flight a
# matrix operand): the widest column block of the matrix under this
BLOCK_BYTES = 4 << 20

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
    """Columns of a matrix `[n, k]` one block holds: the largest multiple
    of 128 that divides `n` and keeps the block under `BLOCK_BYTES`; all of
    `n` where it is no multiple of 128 (a whole axis is always a legal
    block)."""
    if n % 128:
        return n
    lanes = n // 128
    fit = max(1, BLOCK_BYTES // (128 * k * itemsize))
    return 128 * max(d for d in range(1, lanes + 1)
                     if lanes % d == 0 and d <= fit)


def _kernel(group_ref, tile_ref, start_ref, end_ref, x_ref, *refs,
            parts: int, row_tile: int, gated: bool, precision):
    del group_ref
    *w_refs, o_ref = refs
    i = pl.program_id(1)
    x = x_ref[...]                                  # [parts * row_tile, K]

    def product(w_ref):
        whole = jax.lax.dot_general(
            x, w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        out = whole[:row_tile]
        for part in range(1, parts):
            out = out + whole[part * row_tile:(part + 1) * row_tile]
        return out

    y = product(w_refs[0])
    if gated:
        y = jax.nn.silu(y) * product(w_refs[1])
    row = tile_ref[i] * row_tile + jax.lax.broadcasted_iota(
        jnp.int32, y.shape, 0)
    owned = (row >= start_ref[i]) & (row < end_ref[i])
    o_ref[...] = jnp.where(owned, y, o_ref[...])


def grouped_matmul(x: jax.Array, weights: Sequence[jax.Array], items: Items,
                   *, row_tile: int, parts: int = 1, precision=None,
                   interpret: bool = False) -> jax.Array:
    """Rows times their groups' matrices -> float32 `[tiles * row_tile, N]`.

    `x` `[tiles * parts * row_tile, K]`: the sorted rows a tile at a time,
    a tile's `parts` next to each other (module docstring). `weights`: one
    stack `[G, N, K]`, contracted on its last axis (`nn.Linear` layout as
    stored), or two, and the result is `silu(x . first) * (x . second)`:
    the gate and up products of a SwiGLU in one walk. Rows no item owns are
    whatever the buffer held."""
    (n, k), n_w = weights[0].shape[1:], len(weights)
    tiles = x.shape[0] // (parts * row_tile)
    tn = column_block(n, k, weights[0].dtype.itemsize)

    def w_index(j, i, group, tile, start, end):
        return group[i], j, 0

    def x_index(j, i, group, tile, start, end):
        return tile[i], 0

    def o_index(j, i, group, tile, start, end):
        return tile[i], j

    return pl.pallas_call(
        functools.partial(_kernel, parts=parts, row_tile=row_tile,
                          gated=n_w == 2, precision=precision),
        out_shape=jax.ShapeDtypeStruct((tiles * row_tile, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((parts * row_tile, k), x_index)]
            + [pl.BlockSpec((None, tn, k), w_index)] * n_w,
            out_specs=pl.BlockSpec((row_tile, tn), o_index),
            grid=(n // tn, items.count)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(items.group, items.tile, items.start, items.end, x, *weights)
