"""Block-scaled int8 matmul: the compute half of the quantization story.

Every quantized path before this one moves bytes (DCN edges, ICI
collectives, KV ship) while the math stays bf16/f32. This kernel runs the
matmul itself on int8 operands: per-output-channel symmetric weight scales,
per-(row, k-block) symmetric activation scales, int8 x int8 -> int32
accumulation on the MXU (`preferred_element_type=jnp.int32`), dequant in
the epilogue. The k-blocking matters for accuracy: one activation outlier
only poisons its own 128-wide block instead of the whole row (the same
block-scaling rationale as ops/qcollectives.py's codec).

Grid is (m, n, k) with k innermost, so the f32 VMEM scratch accumulator is
zeroed at k==0 and the per-channel weight scale + bias epilogue fires at
the last k step (`@pl.when`) — the canonical sequential-k accumulate shape.
The per-k-block activation scale is applied as each int32 partial product
lands in the accumulator, which is what makes the scales per-BLOCK rather
than per-row: s_x[m, kb] * s_w[n] * (x_q[m, kb*bk:...] @ w_q[...]).

Mode selection (`PIPEEDGE_INT8_MATMUL`, mirroring ops/fused_quant.py):
- `auto` (default): native Pallas kernel on TPU (a lowering error is an
  error); the block-scaled XLA reference path elsewhere (same math, so
  CPU CI and a chip run do the identical quantization).
- `interpret`: Pallas kernel in interpret mode — the CPU CI path that
  keeps the kernel's math honest without TPU hardware.
- `1`/`0`: force the kernel / force the XLA reference.

The wire tunnel (`wire_dense`): an 8-bit `QuantizedTensor` coming off the
DCN edge codec (ops/quant.py affine layout: x = q/255*scale + shift per
outer item) is consumed DIRECTLY by the next stage's first matmul — the
packed bytes are unpacked, recentered to signed int8 (q - 128), and fed to
the same block-scaled kernel; the affine correction folds into a rank-1
epilogue term:

    y = (scale/255) * (q-128) @ W  +  (128*scale/255 + shift) * colsum(W)

so the activation never round-trips through a dequantized f32 tensor
between one stage's MXU and the next's. The producer side needs no new
code: the stage's last matmul emits f32 that the existing fused quant
epilogue (ops/fused_quant.py, bit-identical to the wire codec) packs in
the same jit.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import quant as quant_ops
from ._blocks import pick_block

ENV_INT8_MATMUL = "PIPEEDGE_INT8_MATMUL"

# default k-block width: one lane tile — fine enough that a single
# activation outlier saturates only 128 values, coarse enough that the
# scale sidecar stays 1/128th of the activation bytes
DEFAULT_BLOCK_K = 128


# --------------------------------------------------------------------------
# quantizers (shared by the kernel path, the XLA reference, and calibration)
# --------------------------------------------------------------------------

def quantize_weight(w: jax.Array):
    """Per-output-channel symmetric int8: scale[n] = amax(w[:, n]) / 127.

    All-zero channels get scale 1 (their quantized column is all zeros, so
    any non-zero scale decodes them exactly); round-half-even matches the
    wire codec's rounding.
    """
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, jnp.float32(1))
    w_q = jnp.clip(jnp.round(wf / scale[None, :]), -127, 127).astype(jnp.int8)
    return w_q, scale


def quantize_act_blocks(x: jax.Array, block_k: int):
    """Per-(row, k-block) symmetric int8 over [M, K] activations.

    Returns (x_q int8 [M, K], x_scale f32 [M, K//block_k]). All-zero
    blocks get scale 1; saturating outliers clip at +/-127 (the clamp
    calibration in utils/calibrate.py bounds how often that happens).
    """
    m, k = x.shape
    kb = k // block_k
    xf = x.astype(jnp.float32).reshape(m, kb, block_k)
    amax = jnp.max(jnp.abs(xf), axis=2)
    scale = jnp.where(amax > 0, amax / 127.0, jnp.float32(1))
    x_q = jnp.clip(jnp.round(xf / scale[:, :, None]),
                   -127, 127).astype(jnp.int8)
    return x_q.reshape(m, k), scale


# --------------------------------------------------------------------------
# the kernel and its XLA reference
# --------------------------------------------------------------------------

def _matmul_kernel(x_ref, xs_ref, w_ref, ws_ref, o_ref, acc_ref):
    """One (m, n) tile, accumulated over the innermost k grid dimension.

    x_ref  [bm, bk] int8      xs_ref [bm, K/bk] f32 (the rows' scales)
    w_ref  [bk, bn] int8      ws_ref [1, bn]  f32 (per-channel scales)
    o_ref  [bm, bn] f32       acc_ref [bm, bn] f32 VMEM scratch
    """
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # integer operands have one precision; naming it keeps a process-wide
    # `jax_default_matmul_precision` (Mosaic refuses fp32 passes over int8)
    # out of the kernel
    prod = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32)
    # the scale block spans every k-block (a [bm, 1] block breaks the TPU
    # block-shape rule), so this step's column is picked by a lane mask
    xs = xs_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, xs.shape, 1) == kk
    xs_k = jnp.sum(jnp.where(col, xs, 0.0), axis=1, keepdims=True)
    acc_ref[...] += prod.astype(jnp.float32) * xs_k

    @pl.when(kk == pl.num_programs(2) - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...] * ws_ref[...]


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def matmul_pallas(x_q: jax.Array, x_scale: jax.Array, w_q: jax.Array,
                  w_scale: jax.Array, block_k: int,
                  interpret: bool = False) -> jax.Array:
    """Block-scaled int8 matmul via the Pallas kernel. [M,K]x[K,N] -> f32."""
    m, k = x_q.shape
    n = w_q.shape[1]
    if k % block_k:
        raise ValueError(f"K={k} not divisible by block_k={block_k}")
    bm = pick_block(m, 128)
    bn = pick_block(n, 128)
    grid = (m // bm, n // bn, k // block_k)
    return pl.pallas_call(
        _matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, k // block_k), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((block_k, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x_q, x_scale, w_q, w_scale.reshape(1, n))


def matmul_xla(x_q: jax.Array, x_scale: jax.Array, w_q: jax.Array,
               w_scale: jax.Array, block_k: int) -> jax.Array:
    """Same block-scaled math as the kernel, in plain XLA ops.

    Used as the parity reference in tests and as the dispatch fallback off
    TPU — int8 dots with int32 accumulation lower fine on CPU, they just
    don't hit an MXU.
    """
    m, k = x_q.shape
    n = w_q.shape[1]
    kb = k // block_k
    prod = jax.lax.dot_general(
        x_q.reshape(m, kb, block_k).transpose(1, 0, 2),
        w_q.reshape(kb, block_k, n),
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)                    # [kb, m, n]
    y = jnp.sum(prod.astype(jnp.float32) * x_scale.T[:, :, None], axis=0)
    return y * w_scale[None, :]


# --------------------------------------------------------------------------
# dispatch (the fused_quant mode idiom)
# --------------------------------------------------------------------------

def _mode() -> str:
    return os.getenv(ENV_INT8_MATMUL, "auto").strip().lower()


def kernel_available() -> bool:
    """Whether `matmul_q` will run the Pallas kernel under the current
    `PIPEEDGE_INT8_MATMUL` mode and backend."""
    mode = _mode()
    if mode in ("0", "off", "xla"):
        return False
    if mode in ("1", "on", "interpret"):
        return True
    return jax.default_backend() == "tpu"


def matmul_q(x_q: jax.Array, x_scale: jax.Array, w_q: jax.Array,
             w_scale: jax.Array, block_k: int) -> jax.Array:
    """Dispatch seam: Pallas kernel when available, XLA reference else —
    identical block-scaled math either way."""
    if kernel_available():
        return matmul_pallas(x_q, x_scale, w_q, w_scale, block_k,
                             interpret=_mode() == "interpret")
    return matmul_xla(x_q, x_scale, w_q, w_scale, block_k)


# --------------------------------------------------------------------------
# layer entry points
# --------------------------------------------------------------------------

def int8_dense(x: jax.Array, w: jax.Array, b=None, *,
               block_k: int = DEFAULT_BLOCK_K, clamp_alpha=None,
               out_dtype=None) -> jax.Array:
    """y = x @ w (+ b) with int8 compute, over [..., K] activations.

    `clamp_alpha` (from the calibration sidecar, utils/calibrate.py) clips
    activations to the Banner-optimal +/-alpha before quantization so a
    rare outlier doesn't stretch its block's scale; None skips the clip.
    Weights are quantized per-channel at trace time — under jit with
    traced params that recomputes per call, which XLA fuses but does not
    cache; serving paths that care pre-fold via `quantize_weight`.
    """
    orig_shape = x.shape
    k = orig_shape[-1]
    n = w.shape[1]
    x2 = x.reshape(-1, k)
    bk = pick_block(k, block_k)
    if clamp_alpha is not None:
        alpha = jnp.float32(clamp_alpha)
        x2 = jnp.clip(x2.astype(jnp.float32), -alpha, alpha)
    x_q, x_scale = quantize_act_blocks(x2, bk)
    w_q, w_scale = quantize_weight(w)
    y = matmul_q(x_q, x_scale, w_q, w_scale, bk)
    if b is not None:
        y = y + b
    if out_dtype is None:
        out_dtype = x.dtype
    return y.reshape(*orig_shape[:-1], n).astype(out_dtype)


def wire_dense(p, enc: quant_ops.QuantizedTensor, *,
               block_k: int = DEFAULT_BLOCK_K,
               out_dtype=jnp.float32) -> jax.Array:
    """Consume an 8-bit wire `QuantizedTensor` directly in an int8 matmul.

    The consumer-side half of the stage-seam tunnel: instead of
    decode_outerdim -> f32 dense, the packed bytes feed the MXU as-is.
    Exactness contract (tests/test_int8_matmul.py): the activation side is
    EXACT — the affine identity below loses nothing vs decoding first —
    so the only deviation from `dense(p, decode_outerdim(enc))` is the
    per-channel weight quantization, identical to what `int8_dense` does
    mid-stage.

        x = q/255*scale + shift   (per outer item; ops/quant.py layout)
        y = (scale/255) * ((q-128) @ W_deq)
            + (128*scale/255 + shift) * colsum(W_deq) + b
    """
    if enc.bit != 8:
        raise ValueError(f"wire_dense consumes 8-bit payloads, got bit="
                         f"{enc.bit}")
    shape = enc.shape                       # [items, ..., K]
    items = shape[0]
    k = shape[-1]
    n_per_item = int(np.prod(shape[1:]))
    rows_per_item = n_per_item // k
    m = items * rows_per_item
    n = p["w"].shape[1]
    # unpack uint32 words -> byte values 0..255, per item (the
    # quant_ops._unpack_bits layout: value i at word i//4, offset (i%4)*8)
    shifts = (jnp.arange(4, dtype=jnp.uint32) * 8)[None, None, :]
    vals = (enc.data[:, :, None] >> shifts) & jnp.uint32(0xFF)
    q = vals.reshape(items, -1)[:, :n_per_item]
    qc = (q.astype(jnp.int32) - 128).astype(jnp.int8).reshape(m, k)
    bk = pick_block(k, block_k)
    s = enc.scale.astype(jnp.float32) / 255.0              # [items]
    s_row = jnp.repeat(s, rows_per_item)                   # [m]
    x_scale = jnp.broadcast_to(s_row[:, None], (m, k // bk))
    w_q, w_scale = quantize_weight(p["w"])
    y = matmul_q(qc, x_scale, w_q, w_scale, bk)
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=0).astype(jnp.float32) \
        * w_scale                                          # [n] = colsum(W_deq)
    corr = 128.0 * s + enc.shift.astype(jnp.float32)       # [items]
    y = y + jnp.repeat(corr, rows_per_item)[:, None] * colsum[None, :]
    y = y + p["b"]
    return y.reshape(*shape[:-1], n).astype(out_dtype)
