"""One position of a power-retention layer as a Pallas kernel that updates the
layer's state where it lies in the cache's stack.

A decode step of `models/brumby.py` is, a KV head `g` of a row (`R` query
heads read the one state; `hd` the head's width, `F = (hd / 2 + 1) hd` the
expanded width, `phi` the symmetric second power in the family's layout),
  S' = e^a S + v phi(k')^T            [hd, F]
  z' = e^a z + phi(k')                [F]
  num^h = S' phi(q'^h)                [hd]     den^h = z' . phi(q'^h)
and the state is the largest thing a step touches (34 MB a row a layer at
the published sizes, 2.7 GB at 8 rows of ten layers). As jnp expressions
behind a `dynamic_update_slice` of the donated stack it crosses the HBM
three times (`ops/ssm_step.py` says why). Here a (row, KV head)'s state is
read into VMEM, decayed, `v phi(k')^T` added, reduced against the group's
`R` `phi(q'^h)` and written back to the block it came from: one read and
one write.

**The expansion is made here**, from 128-lane rows: `q'`, `k'` and `v` come
as rows of `hd` lanes (`R + 2` rows a cell), never as rows of `F`. The
family lays `phi` out by diagonals (`models/brumby.py::phi`): entry `d hd +
a` is `c_d x_a x_((a - d) mod hd)`, so diagonal `d` of `phi(x)` is `x` times
`x` rolled `d` lanes, one lane rotation and two products a row of `hd`
lanes, and the state's lanes `[d hd, (d + 1) hd)` meet that row whatever the
sublane. Each diagonal's `R + 1` rows are made once a cell, over the eight
sublanes of a register, and kept in VMEM (`feat`); the walk over the state
then costs a register of state three products and a sum for the update and
two for each query head, nothing on the cross-lane unit.

**In place**, as `ops/ssm_step.py`: the operand is the whole stack `[L, B,
G, hd, F]`, aliased to the first output, the layer a prefetched scalar the
index maps put on the `L` axis. The sum of keys `z` is 0.8% of the state and
comes and goes as the layer's rows `[B, G, F / hd, hd]`, a diagonal a row.

**One grid cell** is one KV head of one row: `[hd, F]` of state (4.26 MB at
the published sizes: in and out, each double-buffered, 17 MB of VMEM), its
decay a scalar from SMEM.

**Precision**: float32 multiplications and sums on the vector unit, nothing
through the matrix unit and no bfloat16 pass: the products and sums of
`brumby.retention_step`, the sums over `F` in the diagonals' order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what the kernel may take of VMEM beside the compiler's own: four blocks of
# a KV head's state and the expanded rows
_VMEM_LIMIT = 64 << 20

# sublanes of a float32 register: the rows of state one trip of the walk holds
_SUBLANES = 8


def diagonals(head_dim: int) -> int:
    """Diagonals `phi` lays a head of `head_dim` out in: distances 0 to
    `head_dim / 2` round the circle of its lanes."""
    return head_dim // 2 + 1


def diagonal_weight(d: int, head_dim: int) -> float:
    """What diagonal `d` of `phi` carries: 1 on the squares and on the
    antipodal diagonal (which holds each of its pairs twice), the root of 2
    on the others (each pair once), so that `phi(q) . phi(k) = (q . k)^2`."""
    return 1.0 if d in (0, head_dim // 2) else 2.0 ** 0.5


def whole_tiles(head_dim: int) -> bool:
    """Whether a head is whole rows of 128 lanes and whole registers of
    sublanes: what the compiled kernel is written for."""
    return head_dim % 128 == 0


def _kernel(layer_ref, decay_ref, s_ref, z_ref, q_ref, k_ref, v_ref,
            o_ref, zo_ref, y_ref, den_ref, feat_ref, vcol_ref, acc_ref):
    del layer_ref
    hd = s_ref.shape[0]
    per_group, n_diag = q_ref.shape[0], z_ref.shape[0]
    decay = decay_ref[pl.program_id(0) * pl.num_programs(1)
                      + pl.program_id(1)]
    # the expanded rows, k' first, each over a register's sublanes; the sum
    # of keys and what it gives each query head on the way
    rows = [jnp.broadcast_to(k_ref[...], (_SUBLANES, hd))] + [
        jnp.broadcast_to(q_ref[h:h + 1, :], (_SUBLANES, hd))
        for h in range(per_group)]
    den = [jnp.zeros((1, hd), jnp.float32)] * per_group
    for d in range(n_diag):
        weight = diagonal_weight(d, hd)
        feats = [x * (pltpu.roll(x, d, 1) if d else x) * weight for x in rows]
        for i, feat in enumerate(feats):
            feat_ref[i, d] = feat
        z_new = decay * z_ref[d:d + 1, :] + feats[0][0:1]
        zo_ref[d:d + 1, :] = z_new
        den = [den[h] + feats[1 + h][0:1] * z_new for h in range(per_group)]
    for h in range(per_group):
        den_ref[h:h + 1, :] = jnp.broadcast_to(
            jnp.sum(den[h], axis=1, keepdims=True), (1, hd))
    # v from its lanes onto the state's sublanes
    vcol_ref[...] = jnp.broadcast_to(v_ref[...], (hd, hd)).T

    def walk(i, carry):     # eight sublanes of the state, every diagonal
        at = pl.multiple_of(i * _SUBLANES, _SUBLANES)
        v_rows = vcol_ref[pl.ds(at, _SUBLANES), :]
        acc = [jnp.zeros((_SUBLANES, hd), jnp.float32)] * per_group
        for d in range(n_diag):
            lanes = slice(d * hd, (d + 1) * hd)
            new = decay * s_ref[pl.ds(at, _SUBLANES), lanes] \
                + v_rows * feat_ref[0, d]
            o_ref[pl.ds(at, _SUBLANES), lanes] = new
            acc = [acc[h] + new * feat_ref[1 + h, d]
                   for h in range(per_group)]
        for h in range(per_group):
            acc_ref[h, pl.ds(at, _SUBLANES), :] = acc[h]
        return carry

    jax.lax.fori_loop(0, hd // _SUBLANES, walk, 0)
    for h in range(per_group):      # the sum over lanes, back onto lanes
        y_ref[h:h + 1, :] = jnp.sum(acc_ref[h].T, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def step(stack: jax.Array, layer, decay: jax.Array, zsum: jax.Array,
         q: jax.Array, k: jax.Array, v: jax.Array, *,
         interpret: bool = False):
    """Layer `layer` (an int32 scalar, possibly traced) of `stack` [L, B, G,
    hd, F] float32 one position on: `decay` = e^a [B, G], `zsum` the layer's
    sum of keys [B, G, F], `q` = q' [B, G, R, hd], `k` = k', `v` [B, G, hd].
    -> (the stack with that layer's state replaced, every other byte as it
    was; the sum of keys after [B, G, F]; num [B, G, R, hd]; den [B, G, R]).

    The stack is aliased to the result: inside a program that owns it (a
    stage program's donated cache) it is updated in place. Jitted, so that a
    program's layers trace and lower the kernel once."""
    _, b, g, hd, width = stack.shape
    per_group, n_diag = q.shape[2], width // hd
    if hd % _SUBLANES or n_diag != diagonals(hd):
        raise ValueError(f"a state of {hd} x {width} is not a head of whole "
                         "registers in phi's layout")

    def s_index(i, j, layer, decay):
        return layer[0], i, j, 0, 0

    def cell(i, j, layer, decay):
        return i, j, 0, 0

    state = pl.BlockSpec((None, None, None, hd, width), s_index)
    sums = pl.BlockSpec((None, None, n_diag, hd), cell)
    heads = pl.BlockSpec((None, None, per_group, hd), cell)
    one = pl.BlockSpec((None, None, 1, hd), cell)
    f32 = jnp.float32
    stack, zsum, num, den = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct(stack.shape, stack.dtype),
                   jax.ShapeDtypeStruct((b, g, n_diag, hd), f32),
                   jax.ShapeDtypeStruct((b, g, per_group, hd), f32),
                   jax.ShapeDtypeStruct((b, g, per_group, hd), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[state, sums, heads, one, one],
            out_specs=(state, sums, heads, heads),
            grid=(b, g),
            scratch_shapes=[
                pltpu.VMEM((1 + per_group, n_diag, _SUBLANES, hd), f32),
                pltpu.VMEM((hd, hd), f32),
                pltpu.VMEM((per_group, hd, hd), f32)]),
        # operand 2 (after the two prefetched scalars) is the stack
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="retention_step",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), decay.reshape(-1), stack,
      zsum.reshape(b, g, n_diag, hd), q, k.reshape(b, g, 1, hd),
      v.reshape(b, g, 1, hd))
    return stack, zsum.reshape(b, g, width), num, den[..., 0]
