"""ctypes wrapper for the native quantized wire codec (native/quantpack.cpp).

Bit-compatible with the XLA ops in `pipeedge_tpu.ops.quant` (same packing
layout and 'original'-mode math), so a payload may be encoded natively on one
host and decoded by the XLA path on another: packed words/scale/shift are
bit-identical for the wire bitwidths (<= 16, the adaptive ladder's range —
reference runtime.py:142-153); decodes agree to f32 rounding (the
quantization error itself is orders of magnitude larger). Used by the DCN
runtime to keep wire encode/decode off the accelerator after device
readback; callers check `available()` and fall back to the XLA ops when no
native toolchain exists — no behavioral difference, only speed.
"""
from __future__ import annotations

import ctypes
import logging
import os
from typing import Optional, Sequence, Tuple

import numpy as np

from ..utils.threads import make_lock

logger = logging.getLogger(__name__)

_LIB_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), 'native', 'build', 'libquantpack.so')

_lib = None
_lib_lock = make_lock("native_quant.lib")
_load_failed = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _load_failed:
            return _lib
        # the scheduler's on-demand cmake build also produces the codec;
        # key the freshness check on OUR artifact, not the sched binary
        from ..sched.scheduler import build_native
        try:
            if build_native(artifact=_LIB_PATH) is None:
                # never a library left over from other sources
                raise OSError("no fresh build of native/quantpack.cpp")
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as exc:
            logger.warning("native quant codec unavailable: %s", exc)
            _load_failed = True
            return None
        lib.qp_abi_version.restype = ctypes.c_int
        if lib.qp_abi_version() != 1:
            logger.warning("native quant codec ABI mismatch; ignoring")
            _load_failed = True
            return None
        u32p = np.ctypeslib.ndpointer(np.uint32, flags='C_CONTIGUOUS')
        f32p = np.ctypeslib.ndpointer(np.float32, flags='C_CONTIGUOUS')
        lib.qp_packed_words.restype = ctypes.c_int64
        lib.qp_packed_words.argtypes = [ctypes.c_int64, ctypes.c_int]
        lib.qp_encode_f32.restype = None
        lib.qp_encode_f32.argtypes = [f32p, ctypes.c_int64, ctypes.c_int64,
                                      ctypes.c_int, u32p, f32p, f32p]
        lib.qp_decode_f32.restype = None
        lib.qp_decode_f32.argtypes = [u32p, ctypes.c_int64, ctypes.c_int64,
                                      ctypes.c_int, f32p, f32p, f32p]
        _lib = lib
        return _lib


def available() -> bool:
    """True when the native codec is loadable (builds it on first call)."""
    return _load() is not None


def encode_outerdim(x: np.ndarray, bit: int) \
        -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Quantize each item along the leading axis (native equivalent of
    ops.quant.tensor_encode_outerdim): returns (packed [b, words] uint32,
    scale [b] f32, shift [b] f32)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native quant codec unavailable")
    if not 0 < bit <= 16:
        raise ValueError("native codec supports wire bitwidths 1..16")
    x = np.ascontiguousarray(x, dtype=np.float32)
    b = x.shape[0]
    n = int(np.prod(x.shape[1:], dtype=np.int64))
    words = lib.qp_packed_words(n, bit)
    packed = np.empty((b, words), np.uint32)
    scale = np.empty((b,), np.float32)
    shift = np.empty((b,), np.float32)
    lib.qp_encode_f32(x.reshape(b, n), b, n, bit, packed, scale, shift)
    return packed, scale, shift


def decode_outerdim(packed: np.ndarray, scale: np.ndarray, shift: np.ndarray,
                    shape: Sequence[int], bit: int) -> np.ndarray:
    """Inverse of `encode_outerdim`; `shape` is the full logical shape
    including the leading (microbatch) axis."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native quant codec unavailable")
    if not 0 < bit <= 16:
        raise ValueError("native codec supports wire bitwidths 1..16")
    shape = tuple(int(s) for s in shape)
    b = shape[0]
    n = int(np.prod(shape[1:], dtype=np.int64))
    packed = np.ascontiguousarray(packed, dtype=np.uint32)
    out = np.empty((b, n), np.float32)
    lib.qp_decode_f32(packed.reshape(b, -1), b, n, bit,
                      np.ascontiguousarray(scale, np.float32),
                      np.ascontiguousarray(shift, np.float32), out)
    return out.reshape(shape)
