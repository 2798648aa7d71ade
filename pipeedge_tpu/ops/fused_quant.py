"""Pallas-fused quant encode/decode: wire encode rides the producing kernel.

`ops/quant.py`'s `tensor_encode_outerdim` is a correct, jittable encoder,
but XLA schedules it as its own fusion after the stage's last matmul: the
full-width activation round-trips HBM once for the matmul output and again
for the quant reduction + pack. These Pallas kernels put the whole per-item
pipeline — min/shift reduction, scale, round, nibble/byte pack into uint32
words — into ONE kernel per item, so the epilogue reads the activation from
HBM exactly once and writes only the packed words + per-item scale/shift
(32/bit of the bytes). The decode kernel is the consumer-prologue mirror.

Bit-identity contract (the acceptance invariant, tests/test_fused_quant.py):
for bit in {4, 8} and any shape, `fused_encode_outerdim(x, bit)` produces
the same packed words, scale, and shift as `quant_ops.tensor_encode_outerdim`
— same f32 op order (min, max-of-shifted, round-half-even, shift-or pack),
same zero-padding of the packed tail — and `fused_decode_outerdim` matches
`tensor_decode_outerdim`. Any producer/consumer therefore pairs with any
other across the fused/XLA/native codec generations (the comm/wire.py
contract).

Kernel layout: the packed word `w` holds values `w*per_word + j` at bit
offset `j*bit` (reference basic_op.py layout). The kernel receives the item
pre-arranged as [per_word, words] — value (j, w) at sublane j, lane w — so
the pack is a per-sublane shift + OR-accumulate down the (static, 4- or
8-deep) sublane axis and the words dimension stays on the 128-wide lanes.
The arranging transpose runs in XLA outside the kernel where layout changes
are free.

Mode selection (`PIPEEDGE_FUSED_QUANT`):
- `auto` (default): fused kernels on TPU backends (a lowering error is an
  error, not a reason to run something else); XLA ops elsewhere.
- `interpret`: fused kernels in Pallas interpret mode — the CPU CI path
  that keeps the kernels' math honest without TPU hardware.
- `1`/`0`: force the fused path / force the XLA ops.

Consumers go through `encode_outerdim`/`decode_outerdim` below — the ONE
dispatch seam `parallel/pipeline.py` (stage epilogue), `parallel/spmd.py`
(ppermute edge codec), `comm/wire.py` (`wire_encode_device`), and
`ops/qcollectives.py` (block-scaled collective codec) all share.
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

ENV_FUSED_QUANT = "PIPEEDGE_FUSED_QUANT"

# bitwidths with a fused kernel: the wire-path workhorses (int8 bytes,
# int4 nibbles). Other bitwidths fall back to the XLA ops.
FUSED_BITS = (4, 8)

# lane-block preference for the decode kernel (per-word sublanes x
# DECODE_LANE_BLOCK lanes of uint32 live in VMEM per grid cell)
DECODE_LANE_BLOCK = 4096


def _encode_kernel(x_ref, data_ref, scale_ref, shift_ref, *, bit: int,
                   n_valid: int):
    """One item: [per_word, words] f32 -> packed words + scale/shift.

    Mirrors `quant_ops._quantize_item` ('original' mode) exactly: the
    reductions run over the n_valid real elements (the tail lanes beyond
    them are padding), quantized padding packs as 0 (the reference pads
    AFTER quantization with zero ints). Words are built in int32 (Mosaic
    has no f32 -> uint32 cast); the caller reinterprets the bits."""
    per_word, words = x_ref.shape[1], x_ref.shape[2]
    x = x_ref[0]                                    # [per_word, words] f32
    j = jax.lax.broadcasted_iota(jnp.int32, (per_word, words), 0)
    w = jax.lax.broadcasted_iota(jnp.int32, (per_word, words), 1)
    valid = w * per_word + j < n_valid
    shift = jnp.min(jnp.where(valid, x, jnp.float32(np.inf)))
    scale = jnp.max(jnp.where(valid, x - shift, jnp.float32(-np.inf)))
    safe_scale = jnp.where(scale > 0, scale, jnp.float32(1))
    x01 = (x - shift) / safe_scale
    levels = float((1 << bit) - 1)
    q = jnp.round(x01 * levels).astype(jnp.int32)
    q = jnp.where(valid, q, 0)
    # disjoint offsets: OR-accumulate the (static) sublane axis into words
    acc = q[0:1, :]
    for jj in range(1, per_word):
        acc = acc | (q[jj:jj + 1, :] << (jj * bit))
    data_ref[0] = acc
    item = pl.program_id(0)
    scale_ref[item] = scale
    shift_ref[item] = shift


def _decode_kernel(scale_ref, shift_ref, data_ref, o_ref, *, bit: int):
    """One (item, lane-block) cell: packed words -> [per_word, words] f32.

    Mirrors `quant_ops._dequantize_item`: unpack by shift+mask, then
    q / levels * scale + shift in the same op order. Words arrive as
    int32 bit patterns (no uint32 -> f32 cast in Mosaic), so the shift is
    the logical one."""
    per_word = 32 // bit
    words = data_ref[0]                             # [1, w_blk] int32
    mask = (1 << bit) - 1
    rows = [(jax.lax.shift_right_logical(words, jnp.int32(jj * bit))
             & mask).astype(jnp.float32) for jj in range(per_word)]
    q = jnp.concatenate(rows, axis=0)               # [per_word, w_blk]
    levels = float((1 << bit) - 1)
    item = pl.program_id(0)
    o_ref[0] = q / levels * scale_ref[item] + shift_ref[item]


@functools.partial(jax.jit, static_argnames=("bit", "interpret"))
def fused_encode_outerdim(x: jax.Array, bit: int,
                          interpret: bool = False) -> quant_ops.QuantizedTensor:
    """Pallas-fused `tensor_encode_outerdim` (bit-identical, bits 4/8)."""
    if bit not in FUSED_BITS:
        raise ValueError(f"fused encode supports bits {FUSED_BITS}, got {bit}")
    shape = tuple(x.shape)
    b = shape[0]
    n = int(np.prod(shape[1:]))
    per_word = 32 // bit
    words = quant_ops.packed_words(n, bit)
    total = words * per_word
    flat = x.reshape(b, n).astype(jnp.float32)
    if total > n:
        flat = jnp.pad(flat, ((0, 0), (0, total - n)))
    # value (j, w) at sublane j, lane w — word index on the wide lane axis
    arranged = flat.reshape(b, words, per_word).transpose(0, 2, 1)
    kernel = functools.partial(_encode_kernel, bit=bit, n_valid=n)
    # per-item outputs: the words ride a [b, 1, words] array so each block's
    # last two dimensions equal the array's (the TPU block-shape rule);
    # scale/shift are scalars, so they live in SMEM, whole, indexed by item
    data, scale, shift = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, words), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.float32),
        ],
        grid=(b,),
        in_specs=[pl.BlockSpec((1, per_word, words), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, words), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        interpret=interpret,
    )(arranged)
    data = jax.lax.bitcast_convert_type(data.reshape(b, words), jnp.uint32)
    return quant_ops.QuantizedTensor(data=data, scale=scale, shift=shift,
                                     shape=shape, bit=bit)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_decode_outerdim(enc: quant_ops.QuantizedTensor,
                          interpret: bool = False) -> jax.Array:
    """Pallas-fused `tensor_decode_outerdim` (bit-identical, bits 4/8)."""
    bit = enc.bit
    if bit not in FUSED_BITS:
        raise ValueError(f"fused decode supports bits {FUSED_BITS}, got {bit}")
    shape = tuple(enc.shape)
    b = shape[0]
    n = int(np.prod(shape[1:]))
    per_word = 32 // bit
    words = enc.data.shape[1]
    # elementwise per lane, so a ragged last block is harmless: its
    # out-of-range lanes are never written back
    w_blk = min(DECODE_LANE_BLOCK, words)
    kernel = functools.partial(_decode_kernel, bit=bit)
    data = jax.lax.bitcast_convert_type(enc.data, jnp.int32)
    full = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, per_word, words), jnp.float32),
        grid=(b, pl.cdiv(words, w_blk)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, w_blk), lambda i, k: (i, 0, k)),
        ],
        out_specs=pl.BlockSpec((1, per_word, w_blk), lambda i, k: (i, 0, k)),
        interpret=interpret,
    )(enc.scale, enc.shift, data.reshape(b, 1, words))
    flat = full.transpose(0, 2, 1).reshape(b, words * per_word)
    return flat[:, :n].reshape(shape)


# -- dispatch seam (pipeline epilogue / spmd edge / wire / collectives) --

def _mode() -> str:
    return os.getenv(ENV_FUSED_QUANT, "auto").strip().lower()


def fused_available(bit: int) -> bool:
    """Whether the fused Pallas path will serve this bitwidth under the
    current `PIPEEDGE_FUSED_QUANT` mode and backend."""
    if bit not in FUSED_BITS:
        return False
    mode = _mode()
    if mode in ("0", "off"):
        return False
    if mode in ("1", "on", "interpret"):
        return True
    # auto: the native kernels on a TPU backend, the XLA ops elsewhere
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return _mode() == "interpret"


def encode_outerdim(x: jax.Array, bit: int,
                    mode: str = "original") -> quant_ops.QuantizedTensor:
    """Per-outer-item encode through the fused kernel when available,
    else the XLA ops — bit-identical either way."""
    if bit and mode == "original" and fused_available(bit):
        return fused_encode_outerdim(x, bit, interpret=_interpret())
    return quant_ops.tensor_encode_outerdim(x, bit, mode)


def decode_outerdim(enc: quant_ops.QuantizedTensor) -> jax.Array:
    """Inverse of `encode_outerdim` (same dispatch rule)."""
    if enc.bit and fused_available(enc.bit):
        return fused_decode_outerdim(enc, interpret=_interpret())
    return quant_ops.tensor_decode_outerdim(enc)
