"""One position of a Mamba-2 layer's recurrence as a Pallas kernel that
updates the layer's state where it lies in the cache's stack.

A decode step of `models/mamba2.py` is, a head of a row,
  S' = a S + (dt x) B^T        [P, N]
  y  = S' C                    [P]
and the state is the largest thing a step touches (537 MB a layer at 128
rows of the published sizes). As two jnp expressions behind a
`dynamic_update_slice` of the donated stack it crosses the HBM three times:
XLA fuses `a S + (dt x) B^T` into the reduction against C AND into the
in-place update rather than write it once more, so the state is read twice
and written once (PERF.md, PR 47 and PR 48). Here a tile of the state is
read into VMEM, updated, reduced against C and written back to the block it
came from: one read and one write.

**In place.** The operand is the whole stack `[L, B, H, P, N]`, aliased to
the first output; the layer is a prefetched scalar that the index maps put
on the `L` axis, so only that layer's blocks are copied in and out and no
other layer's byte is touched. Nothing is sliced out of the stack and
nothing is put back: a caller hands the returned stack on as the cache's
leaf.

**One grid cell** is (a few rows of the batch, one group of heads): the `R`
heads of a group share one `B_t` and one `C_t` (`[N]` a row), so the cell
reads `[rows, R, P, N]` of state, `[rows, R, P]` of `dt x` and two rows of
128 lanes a row of the batch. The decays, one a (row, head), are scalars:
they come through SMEM (scalar prefetch) and meet a head's tile as a splat,
where a vector of them would cost a cross-lane permute a vreg of state.
What is left for the cross-lane unit is what the mathematics asks: `dt x`
from its lanes onto the sublanes of `P` (one permute a vreg of state) and
the sum over `N` (one lane reduction a vreg).

**Precision**: float32 multiplications and sums on the vector unit, nothing
through the matrix unit and no bfloat16 pass; the same three products and
one sum an element as `mamba2.ssm_step`, the sum over `N` in the
hardware's order instead of XLA's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of state one grid cell holds (in and out, each double-buffered: four
# blocks in VMEM): as many rows of the batch as divide it and fit
BLOCK_BYTES = 2 << 20

# what the kernel may take of VMEM beside the compiler's own (the default
# scope of 16 MiB does not hold four blocks and the products' temporaries)
_VMEM_LIMIT = 96 << 20


def row_tile(rows: int, group_bytes: int) -> int:
    """Rows of the batch a grid cell holds: the largest divisor of `rows`
    whose groups of heads (`group_bytes` a row) stay under `BLOCK_BYTES`;
    at least one."""
    fit = max(1, BLOCK_BYTES // group_bytes)
    return max(d for d in range(1, min(rows, fit) + 1) if rows % d == 0)


def whole_tiles(head_dim: int, state: int) -> bool:
    """Whether a head's state `[head_dim, state]` is whole float32 tiles
    (8 sublanes x 128 lanes): what the compiled kernel is written for."""
    return head_dim % 8 == 0 and state % 128 == 0


def _kernel(layer_ref, decay_ref, s_ref, dtx_ref, b_ref, c_ref, o_ref, y_ref):
    del layer_ref
    rows, per_group, hd, n = s_ref.shape
    first, group = pl.program_id(0) * rows, pl.program_id(1)
    for row in range(rows):     # the decays lie [B, G, R], flat
        at = ((first + row) * pl.num_programs(1) + group) * per_group
        decay = jnp.concatenate(
            [jnp.full((1, hd, n), decay_ref[at + head], jnp.float32)
             for head in range(per_group)], axis=0)
        new = s_ref[row] * decay + dtx_ref[row][:, :, None] * b_ref[row][None]
        o_ref[row] = new
        y_ref[row] = jnp.sum(new * c_ref[row][None], axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def step(stack: jax.Array, layer, decay: jax.Array, dtx: jax.Array,
         bm: jax.Array, cm: jax.Array, *, interpret: bool = False):
    """Layer `layer` (an int32 scalar, possibly traced) of `stack` [L, B, H,
    P, N] float32 one position on: `decay` = a [B, H], `dtx` = dt x [B, H,
    P], `bm`, `cm` = B_t, C_t [B, G, N], heads `R g .. R g + R - 1` on group
    `g`. -> (the stack with that layer's state replaced, every other byte as
    it was; y [B, H, P] without the skip).

    The stack is aliased to the result: inside a program that owns it (a
    stage program's donated cache) it is updated in place. Jitted, so that
    a program's layers trace and lower the kernel once (`masked_attention.
    attend`'s reason)."""
    _, b, h, hd, n = stack.shape
    g = bm.shape[1]
    per_group = h // g
    rows = row_tile(b, per_group * hd * n * stack.dtype.itemsize)

    def s_index(i, j, layer, decay):
        return layer[0], i, j, 0, 0

    def head_index(i, j, layer, decay):
        return i, j, 0

    def group_index(i, j, layer, decay):
        return i, j, 0, 0

    state = pl.BlockSpec((None, rows, per_group, hd, n), s_index)
    heads = pl.BlockSpec((rows, per_group, hd), head_index)
    shared = pl.BlockSpec((rows, None, 1, n), group_index)
    return pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct(stack.shape, stack.dtype),
                   jax.ShapeDtypeStruct((b, h, hd), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[state, heads, shared, shared],
            out_specs=(state, heads),
            grid=(b // rows, g)),
        # operand 2 (after the two prefetched scalars) is the stack
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ssm_step",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), decay.reshape(-1), stack,
      dtx, bm.reshape(b, g, 1, n), cm.reshape(b, g, 1, n))
