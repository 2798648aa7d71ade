"""The softmax(q k^T / sqrt(Dh)) v core of an unmasked, non-causal, SHORT
self-attention as one Pallas kernel that reads q, k and v where the
projections wrote them, `[B, S, H * Dh]`, and writes the context in the same
lay-out, ready for the output projection.

XLA's einsums over the same operands (`models/layers.py::einsum_core`) run
the two products well and waste their time around them: q, k and v are each
copied transposed (`%copy bf16[8,197,1024]{1,2,0}`, positions minor-most, so
that the second product can stream a head's 64 as rows; two fifths of the
core's seconds in ViT-L's cells), and the scores of 16 heads pass through
`f32[8,16,197,197]` between two fusions (PERF.md section 6, PR 60). Here a
grid cell is one image: its q, k, v rows `[S, H * Dh]` come into VMEM once,
as blocks the pipeline brings while the image before is computed, and no
score, probability, transposed operand or merged head exists outside the
cell.

**A slab of 128 lanes at a time.** Two heads of 64 (or one of 128) share a
vreg's lanes, and nothing here is shifted across lanes or sliced out of a
vreg. A slab's k and v are staged into scratch `[heads * keys, 128]`: a
head's keys under those of the head before it, its row rounded up to `keys`
whole lanes, the other head's lanes and the rows past S ZERO (what lies
beyond a block's edge is undefined, and 0 x NaN is NaN). q's slab times
that, contracting both operands' last axis (no transposed k), is `[S,
heads * keys]`: each head's scores beside the other's in whole vregs, the
other head's lanes of q meeting zeros. The keys past S get a bias of -1e30
on the last 128 columns; a float32 softmax a head over its whole row (S is
a few hundred: no streaming, no running maximum); the probabilities, cast
to the operands' type, side by side times the staged v add up to each
head's context in its own lanes, accumulated in float32. That is the
einsums' arithmetic (operands in their own type into the matrix unit, the
scale on the float32 scores, the cast of the probabilities before the second
product); the scale is a product with 1 / sqrt(Dh), exact for 64, and the
softmax's division a product with the sum's reciprocal (the unit's estimate
and one Newton step), an ulp of float32 from a division.

**Three walks of an image's slabs, through scratch.** Every slab's scores,
then every softmax, then every context, each slab with score, probability
and staging buffers of its OWN. Both are what the chip asked for (my chip
runs, PR 60, calls 203 and 204; PERF.md section 6). The compiler keeps the order of
scratch accesses as written, so only the walks leave it a slab's softmax to
run under another slab's product: a slab at a time (score, softmax, context,
then the next slab) took 45.3 us at ViT-L's call in a chain of cores where
the three walks took 31.5, and made the four-chip ViT cell 3,897 img/s for
4,066. And a buffer staged for two slabs inside one body
gave wrong scores on the chip (0.66-0.75 of their range, bfloat16, every
shape tried; right in float32), where interpret mode is exact: Mosaic (jax
0.9.0) does not hold a bfloat16 store to scratch behind the matrix unit's
earlier reads of it. Within a cell every buffer here is written, then read,
and not written again (the first cell zeroes `k_pad` and `v_pad` before it
stages into them: two writes, both before any read); the next cell's staging
follows this cell's reads across the grid's step. `chip_smoke.py`'s probe
holds the kernel to the einsums on the chip at every kind of call `takes`
admits, which is where such a fault would show.

**What it asks of VMEM** is what it needs (`vmem_bytes`) and `VMEM_MARGIN`
for spilled values, not the chip's whole: the compiler plans its own buffers
(the weights it prefetches, the activations it keeps near) around a custom
call's limit, so what a kernel asks for and does not use is taken from its
NEIGHBOURS in the program: this body with a limit of 100 MiB made the
four-chip ViT cell 3,801 img/s for 4,066, under the einsums' 3,905 (my chip
run, PR 60, call 204).

**The gradient.** A `pallas_call` has no transpose: `short_attention` is a
`jax.custom_vjp`, forward the kernel, backward the VJP of the einsum core
(`layers.einsum_core`, handed in by the caller: one core, so the forward's
fallback and the backward cannot drift apart) on the saved q, k, v: the same
mathematics, the scores computed again, which `--remat` does anyway.

Who takes it is `takes`'s and `models/layers.py::self_attention`'s to say;
the tests run it in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128             # a vreg's lanes: two heads of 64, one head of 128
_MASKED = -1e30         # a key past the row: exp() of it is an exact zero

# what a call's blocks and scratch may take of VMEM (`vmem_bytes`): the 16
# MiB a kernel is given by default. ViT-L's call takes 10.2 MB of it and rows
# of 256 are the longest that fit at its width (PERF.md section 6, PR 60: the
# table of calls; the rule is measured in a cell at 197 rows only)
VMEM_BUDGET = 16 << 20
# beside them, for the values the compiler spills (module docstring)
VMEM_MARGIN = 8 << 20


def _staged_rows(seq_len: int, head_dim: int) -> int:
    """Rows of a slab's staged k (and columns of its scores): its heads'
    key rows one under the other, each rounded up to whole lanes."""
    return LANES // head_dim * (-(-seq_len // LANES) * LANES)


def vmem_bytes(seq_len: int, width: int, head_dim: int, itemsize: int) -> int:
    """VMEM a call of `seq_len` positions and `width` = heads x `head_dim`
    columns holds: the blocks of q, k, v and the context, two of each for
    the pipeline, and every slab's scratch (float32 scores, probabilities,
    staged k and v)."""
    wide = _staged_rows(seq_len, head_dim)
    slab = seq_len * wide * (4 + itemsize) + 2 * wide * LANES * itemsize
    return 8 * seq_len * width * itemsize + width // LANES * slab


def _whole_slabs(width: int, head_dim: int) -> bool:
    """Heads that are lane slices of whole slabs: 64 or 128 wide, `width` a
    multiple of 128 (the kernel tells a slab's heads apart by their lanes)."""
    return head_dim in (LANES // 2, LANES) and width % LANES == 0


def takes(seq_len: int, width: int, head_dim: int, itemsize: int) -> bool:
    """Whether the kernel is for a call of `seq_len` positions and `width` =
    heads x `head_dim` columns of `itemsize` bytes: `_whole_slabs`, and
    blocks and scratch within `VMEM_BUDGET`."""
    return (_whole_slabs(width, head_dim)
            and vmem_bytes(seq_len, width, head_dim, itemsize) <= VMEM_BUDGET)


def _reciprocal(total: jax.Array) -> jax.Array:
    """1 / total for a softmax's sums (finite, at least 1): the unit's
    estimate and one Newton step, where a float32 division also handles
    infinities, zeros and denormals, a dozen vector operations a vreg."""
    estimate = pl.reciprocal(total, approx=True)
    return estimate * (2.0 - total * estimate)


def _kernel(q_ref, k_ref, v_ref, o_ref, k_pad, v_pad, s_buf, p_buf, *,
            head_dim: int):
    """A grid cell, an image: q, k, v, o `[S, width]` blocks; `k_pad`,
    `v_pad` `[slabs, heads * keys, 128]`, `s_buf` (float32) and `p_buf`
    `[slabs, S, heads * keys]` scratch (module docstring).

    Scratch outlives a cell and the cells of a call run in turn on the one
    core (the grid's axis is "arbitrary"): the first zeroes what no staging
    writes, the other head's lanes and the rows past S; every image then
    stages into the same rows and lanes."""
    s, width = q_ref.shape
    dtype = o_ref.dtype
    heads = LANES // head_dim
    keys = k_pad.shape[1] // heads
    tail = keys - LANES             # the last 128 keys: the only padded ones
    scale = jnp.float32(1.0 / head_dim ** 0.5)
    slabs = range(width // LANES)

    @pl.when(pl.program_id(0) == 0)
    def _():
        k_pad[...] = jnp.zeros_like(k_pad)
        v_pad[...] = jnp.zeros_like(v_pad)

    if keys > s:
        bias = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) + tail < s,
            0.0, _MASKED).astype(jnp.float32)
    for n in slabs:
        for h in range(heads):
            rows, cols = pl.ds(h * keys, s), pl.ds(h * head_dim, head_dim)
            head = pl.ds(n * LANES + h * head_dim, head_dim)
            k_pad[n, rows, cols] = k_ref[:, head]
            v_pad[n, rows, cols] = v_ref[:, head]
        s_buf[n] = jax.lax.dot_general(
            q_ref[:, pl.ds(n * LANES, LANES)], k_pad[n],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    for n in slabs:
        scores = s_buf[n]
        probs = []
        for h in range(heads):
            sc = scores[:, h * keys:(h + 1) * keys] * scale
            if keys > s:        # Mosaic refuses a slice of no lanes
                masked = sc[:, tail:] + bias
                sc = jnp.concatenate([sc[:, :tail], masked], axis=1) \
                    if tail else masked
            weights = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True))
            total = jnp.sum(weights, axis=-1, keepdims=True)
            probs.append((weights * _reciprocal(total)).astype(dtype))
        p_buf[n] = jnp.concatenate(probs, axis=1)
    for n in slabs:
        ctx = jax.lax.dot_general(
            p_buf[n], v_pad[n], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[:, pl.ds(n * LANES, LANES)] = ctx.astype(dtype)


@functools.partial(jax.jit, static_argnames=("num_heads", "interpret"))
def _call(q, k, v, *, num_heads, interpret):
    b, s, width = q.shape
    head_dim = width // num_heads
    assert _whole_slabs(width, head_dim), (width, num_heads)
    slabs, wide = width // LANES, _staged_rows(s, head_dim)
    # a block that spans the array's whole extent may have rows that are no
    # multiple of 8: no q, k or v is padded in HBM
    image = pl.BlockSpec((None, s, width), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, head_dim=head_dim),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b,),
        in_specs=[image, image, image],
        out_specs=image,
        scratch_shapes=[pltpu.VMEM((slabs, wide, LANES), q.dtype),
                        pltpu.VMEM((slabs, wide, LANES), q.dtype),
                        pltpu.VMEM((slabs, s, wide), jnp.float32),
                        pltpu.VMEM((slabs, s, wide), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(s, width, head_dim, q.dtype.itemsize)
            + VMEM_MARGIN),
        name="short_attention",
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def short_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    num_heads: int, reference,
                    interpret: bool = False) -> jax.Array:
    """softmax(q k^T / sqrt(Dh)) v over q, k, v `[B, S, H * Dh]` as `dense`
    wrote them -> the context `[B, S, H * Dh]` as the output projection
    reads it, for the calls `takes` (module docstring). Differentiable: the
    backward is `reference`'s, the caller's einsum core over `[B, S, H, Dh]`
    (`models/layers.py::einsum_core`), from q, k and v."""
    return _call(q, k, v, num_heads=num_heads, interpret=interpret)


def _forward(q, k, v, num_heads, reference, interpret):
    return short_attention(q, k, v, num_heads, reference, interpret), (q, k, v)


def _backward(num_heads, reference, interpret, saved, grad):
    del interpret
    flat = saved[0].shape
    heads = (*flat[:2], num_heads, flat[2] // num_heads)
    return jax.vjp(
        lambda *qkv: reference(*(x.reshape(heads) for x in qkv)).reshape(flat),
        *saved)[1](grad)


short_attention.defvjp(_forward, _backward)
