"""Selection-masked attention of one KV group as a streaming Pallas kernel.

A span of a sparse decoder family (keye's learned top-k of keys, MiniCPM-SALA's
kept blocks) attends a cached window and its own rows under a mask a query.
The XLA path (`models/decoder.py::softmax_over`) writes a chunk's float32
scores `[B, r, Q, K]` to HBM, reads them for the softmax, writes the weights
and reads them for the context. Here a key block meets all of a group's query
heads in VMEM and the scores never leave it (online softmax; the design is
JAX's splash attention for TPU, with float32 operands and a mask that is data,
not a pattern known at trace time).

**One grid cell** is (row of the batch, query tile, key block). The `r` query
heads of the KV group times `bq` queries are the `r * bq` rows of ONE tile
(head-major: row `h * bq + q`), so a key block `[bk, Dh]` is read once for
all of them and its mask block `[bq, bk]` is a free broadcast over the
leading axis. The key axis is the grid's last: running max, sum and
accumulator stay in VMEM scratch between its steps, and the last step
divides the accumulator by the sum.

**Blocks that hold no kept key are skipped, compute and DMA.** Two short
tables a (row, query tile, key block), reduced from the mask outside the
kernel and prefetched into SMEM: `run`, whether any key of the block is kept
for any query of the tile, guards the body (`pl.when`); `fetch`, the latest
block at or before this one that runs (the first that runs, before it), is
what the index maps of `k`, `v` and the mask return, so a skipped block
repeats its neighbour's index and the pipeline copies nothing.

**Precision**: every product is `Precision.HIGHEST` on float32 operands, which
Mosaic lowers to the six bfloat16 passes XLA's HIGHEST is on this chip
(`#tpu.contract_precision<fp32>`); `exp` in float32; the weights meet the
values before anything is divided by their sum.

**Parts.** The keys come in parts (the cached window, the span's own rows),
each an operand of its own, read where it lies: the grid's last axis walks
the parts' blocks one part after another, a part's index maps standing still
on its first or last block while another part's are walked, and the one
running softmax spans them all. Nothing is put beside anything else first,
and no partial sum leaves the kernel.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what a masked score is, as in the XLA path: exp(_MASKED - max) is exactly 0
_MASKED = -1e30
_LANES = 128

# queries and keys a tile: `r * QUERY_TILE` rows meet `KEY_BLOCK` keys in one
# step (keye: 8 x 128 = 1,024 rows; SALA: 16 x 128 = 2,048). An int8 mask
# block wants whole tiles of 32 sublanes
QUERY_TILE = 128
KEY_BLOCK = 512
_MASK_SUBLANES = 32

# what the kernel may take of VMEM beside the compiler's own: the scores and
# weights of a step are `rows x KEY_BLOCK` float32 each, with their bfloat16
# parts beside them (the default scope of 16 MiB does not hold SALA's tile)
_VMEM_LIMIT = 96 << 20


def key_block(n_keys: int) -> int:
    """Keys one step reads: the largest multiple of 128 lanes that divides
    `n_keys`, at most `KEY_BLOCK`; 0 where `n_keys` is no multiple of 128
    (the caller keeps the XLA path)."""
    if n_keys % _LANES:
        return 0
    lanes = n_keys // _LANES
    return _LANES * max(d for d in range(1, KEY_BLOCK // _LANES + 1)
                        if lanes % d == 0)


def query_tile(n_q: int) -> Tuple[int, int]:
    """(queries a tile, queries after padding): whole mask tiles of 32, at
    most `QUERY_TILE`, dividing the padded count."""
    padded = -(-n_q // _MASK_SUBLANES) * _MASK_SUBLANES
    tiles = padded // _MASK_SUBLANES
    return _MASK_SUBLANES * max(
        d for d in range(1, QUERY_TILE // _MASK_SUBLANES + 1)
        if tiles % d == 0), padded


def block_tables(keeps: Sequence[jax.Array], bq: int, bks: Sequence[int]):
    """`run` and `fetch` (module docstring) of the parts' masks, a [B, Q, K]
    a part: int32 [B * Q/bq * blocks of all parts] each, a key block the
    fastest axis, a part's blocks after the part's before it; `fetch` counts
    within its part."""
    runs, fetches = [], []
    for keep, bk in zip(keeps, bks):
        b, n_q, n_k = keep.shape
        nq, nk = n_q // bq, n_k // bk
        run = jnp.any(keep.reshape(b, nq, bq, nk, bk), axis=(2, 4))
        at = jnp.arange(nk, dtype=jnp.int32)
        last = jax.lax.cummax(jnp.where(run, at, -1), axis=2)
        first = jnp.argmax(run, axis=2).astype(jnp.int32)[..., None]
        runs.append(run.astype(jnp.int32))
        fetches.append(jnp.where(last < 0, first, last))
    return (jnp.concatenate(runs, axis=2).reshape(-1),
            jnp.concatenate(fetches, axis=2).reshape(-1))


def _kernel(run_ref, fetch_ref, q_ref, *refs, scale: float, starts: tuple):
    del fetch_ref
    *part_refs, out_ref, acc_s, top_s, sum_s = refs
    _, heads, bq, hd = q_ref.shape
    rows = heads * bq
    row, tile, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, n_blocks = pl.num_programs(1), pl.num_programs(2)
    exact = functools.partial(
        jax.lax.dot_general, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _():
        acc_s[...] = jnp.zeros_like(acc_s)
        top_s[...] = jnp.full_like(top_s, _MASKED)
        sum_s[...] = jnp.zeros_like(sum_s)

    def block(k_ref, v_ref, keep_ref):
        bk = k_ref.shape[1]
        q = q_ref[0].reshape(rows, hd)
        scores = exact(q, k_ref[0], (((1,), (1,)), ((), ()))) * scale
        kept = keep_ref[0].astype(jnp.int32) != 0                # [bq, bk]
        scores = jnp.where(kept[None], scores.reshape(heads, bq, bk),
                           _MASKED).reshape(rows, bk)
        top_prev = top_s[...]
        top = jnp.maximum(top_prev, jnp.max(scores, axis=-1, keepdims=True))
        weights = jnp.exp(scores - top)
        shrink = jnp.exp(top_prev - top)
        sum_s[...] = shrink * sum_s[...] + jnp.sum(weights, axis=-1,
                                                   keepdims=True)
        acc_s[...] = shrink * acc_s[...] + exact(
            weights, v_ref[0], (((1,), (0,)), ((), ())))
        top_s[...] = top

    runs = run_ref[(row * nq + tile) * n_blocks + j] != 0
    for part, (start, end) in enumerate(zip(starts[:-1], starts[1:])):
        pl.when(runs & (j >= start) & (j < end))(functools.partial(
            block, *part_refs[3 * part:3 * part + 3]))

    @pl.when(j == n_blocks - 1)
    def _():
        out_ref[0] = (acc_s[...] / sum_s[...]).reshape(heads, bq, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def attend(q: jax.Array, ks: Sequence[jax.Array], vs: Sequence[jax.Array],
           keeps: Sequence[jax.Array], *, interpret: bool = False):
    """One softmax of q [B, r, Q, Dh] float32 (a KV group's query heads,
    head-major) over the key parts ks, vs (a [B, K, Dh] float32 a part, `K`
    a multiple of 128: `key_block`) under the masks keeps (a bool [B, Q, K]
    a part). Every query keeps a key of some part. -> [B, r, Q, Dh].

    Jitted, so that a program whose KV groups call it with like shapes
    traces and lowers the kernel once: a `pallas_call` costs a span program
    0.05-0.1 s of set-up each time it is traced and lowered, which the
    persistent cache does not save (PERF.md, PR 45)."""
    b, heads, n_q, hd = q.shape
    bks = [key_block(k.shape[1]) for k in ks]
    bq, padded = query_tile(n_q)
    if padded != n_q:
        # a padded query keeps nothing: its row of the result is not read
        q = jnp.pad(q, ((0, 0), (0, 0), (0, padded - n_q), (0, 0)))
        keeps = [jnp.pad(keep, ((0, 0), (0, padded - n_q), (0, 0)))
                 for keep in keeps]
    nq = padded // bq
    starts = [0]
    for k, bk in zip(ks, bks):
        starts.append(starts[-1] + k.shape[1] // bk)
    n_blocks = starts[-1]
    run, fetch = block_tables(keeps, bq, bks)

    def q_index(row, tile, j, run, fetch):
        return row, 0, tile, 0

    def part_specs(start, end, bk):
        def at(row, tile, j, fetch):
            return fetch[(row * nq + tile) * n_blocks
                         + jnp.clip(j, start, end - 1)]

        def kv_index(row, tile, j, run, fetch):
            return row, at(row, tile, j, fetch), 0

        def keep_index(row, tile, j, run, fetch):
            return row, tile, at(row, tile, j, fetch)

        return [pl.BlockSpec((1, bk, hd), kv_index),
                pl.BlockSpec((1, bk, hd), kv_index),
                pl.BlockSpec((1, bq, bk), keep_index)]

    rows = heads * bq
    operands = [x for k, v, keep in zip(ks, vs, keeps)
                for x in (k, v, keep.astype(jnp.int8))]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=hd ** -0.5, starts=tuple(starts)),
        out_shape=jax.ShapeDtypeStruct((b, heads, padded, hd), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((1, heads, bq, hd), q_index)] + [
                spec for start, end, bk in zip(starts[:-1], starts[1:], bks)
                for spec in part_specs(start, end, bk)],
            out_specs=pl.BlockSpec((1, heads, bq, hd), q_index),
            grid=(b, nq, n_blocks),
            scratch_shapes=[pltpu.VMEM((rows, hd), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="masked_attention",
        interpret=interpret,
    )(run, fetch, q, *operands)
    return out[:, :, :n_q]
