"""KV-page shipping: finished prefill KV rows as wire frames.

The disaggregation split (kv/disagg.py) runs prompt passes on a PREFILL
fleet and decode waves on a DECODE fleet; what travels between them is
each request's per-stage KV rows `[n_blocks, B, prompt_len, H*Dh]`
plus the last stage's final-position logits `[B, V]` (the pick stays on
the decode side, with the request's own rng — disaggregated tokens are
identical to colocated ones).

The payload rides the SAME wire-v2 device-encoded frames activations
already use (comm/wire.py): one v2 frame per stage — int8 block-scaled
quads at `bits=8` (4x fewer KV bytes on the wire, the PR 6/9 codec
lineage, bit-identical packing across the XLA/native/fused encoders),
raw arrays at `bits=0` (exact; the parity-acceptance setting) — with
the optional CRC integrity trailer (PIPEEDGE_WIRE_CRC) verified on
decode like any other v2 frame. `frames_to_bytes`/`frames_from_bytes`
give the byte-stream form for the socket path; a colocated prefill
fleet hands the arrays over in-process instead (the transport-tier
split of docs/DCN_WIRE.md applied to KV).

Logits always ship exact (bit 0): quantizing the pick's input would
change tokens, not just bytes — KV rows are the bandwidth, logits are
one row.
"""
from __future__ import annotations

import io
import socket
import struct
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..comm import wire

# distinct from WIRE_V2_MAGIC (-2): a kv-ship bundle opens with its own
# sentinel so a misrouted frame fails loudly, not as a shape error
KV_SHIP_MAGIC = -7
KV_SHIP_VERSION = 1
_LEAVES = ("k", "v")     # fp cache leaves, in shipped order

SHIP_PATHS = ("local", "wire")


def encode_kv_ship(caches: Sequence[Dict], prompt_len: int, logits,
                   bits: int = 0, crc: Optional[bool] = None) \
        -> List[np.ndarray]:
    """Per-stage dense caches (+ final logits) -> one flat tensor list:
    `[kv_header, logits, stage0 v2 frame..., stage1 v2 frame..., ...]`.
    Only the first `prompt_len` cache positions ship. fp caches only —
    int8 caches' scale rows have no codec lane (and re-quantizing int8
    would compound error); quantize on the WIRE with `bits=8` instead."""
    if prompt_len < 1:
        raise ValueError(f"prompt_len must be >= 1, got {prompt_len}")
    if bits not in (0, 8):
        raise ValueError(f"kv ship bits must be 0 (exact) or 8, "
                         f"got {bits}")
    logits = np.asarray(logits, np.float32)
    if logits.ndim != 2:
        raise ValueError(f"logits must be [B, V], got {logits.shape}")
    frames: List[np.ndarray] = []
    for cache in caches:
        if set(cache) != set(_LEAVES):
            raise ValueError(
                "kv ship covers fp caches (leaves k/v); this cache has "
                f"{sorted(cache)} — int8 CACHES don't ship (use "
                "bits=8 to quantize on the wire instead)")
        rows = tuple(cache[name][:, :, :prompt_len] for name in _LEAVES)
        frames.extend(wire.wire_encode_device(rows, bits,
                                              crc=crc).finalize())
    header = np.asarray([KV_SHIP_MAGIC, KV_SHIP_VERSION, bits,
                         len(caches), prompt_len, logits.shape[0]],
                        np.int64)
    return [header, logits] + frames


def _v2_span(tensors: Sequence[np.ndarray], start: int) -> int:
    """Tensor count of the v2 frame starting at `tensors[start]`."""
    header = np.asarray(tensors[start])
    if not (header.ndim == 1 and header.size >= 5
            and header.dtype.kind == "i"
            and int(header[0]) == wire.WIRE_V2_MAGIC):
        raise ValueError("malformed kv-ship bundle: expected a wire-v2 "
                         f"frame header at tensor {start}")
    bit, flags, n_payload = (int(header[2]), int(header[3]),
                             int(header[4]))
    span = 1 + (n_payload if bit == 0 else 4 * n_payload)
    if flags & wire.FLAG_CRC:
        span += 1
    return span


def decode_kv_ship(tensors: Sequence[np.ndarray], dtype) -> dict:
    """Inverse of `encode_kv_ship`: returns the install handle
    `{"stage_rows": [{k, v} per stage], "logits", "prompt_len"}`
    (kv/backend.py `_install_shipped`'s input). CRC-flagged frames are
    verified; corruption raises `wire.WireCorruptError`."""
    header = np.asarray(tensors[0])
    if not (header.ndim == 1 and header.size >= 6
            and int(header[0]) == KV_SHIP_MAGIC):
        raise ValueError("not a kv-ship bundle (bad magic header)")
    if int(header[1]) != KV_SHIP_VERSION:
        raise ValueError(f"kv-ship version {int(header[1])} "
                         f"(this decoder speaks {KV_SHIP_VERSION})")
    n_stages, prompt_len = int(header[3]), int(header[4])
    logits = np.asarray(tensors[1], np.float32)
    stage_rows: List[Dict] = []
    at = 2
    for _ in range(n_stages):
        span = _v2_span(tensors, at)
        payload = wire.wire_decode(list(tensors[at:at + span]), dtype)
        at += span
        if not isinstance(payload, tuple) or len(payload) != len(_LEAVES):
            raise ValueError("malformed kv-ship stage frame: expected "
                             f"{len(_LEAVES)} payload tensors")
        stage_rows.append(dict(zip(_LEAVES, payload)))
    if at != len(tensors):
        raise ValueError(f"kv-ship bundle has {len(tensors) - at} "
                         "trailing tensor(s)")
    return {"stage_rows": stage_rows, "logits": logits,
            "prompt_len": prompt_len}


# -- byte-stream form (the socket path) ----------------------------------

def frames_to_bytes(tensors: Sequence[np.ndarray]) -> bytes:
    """Tensor list -> one bytes blob (npz container, order-preserving)."""
    buf = io.BytesIO()
    np.savez(buf, **{f"t{i}": np.asarray(t)
                     for i, t in enumerate(tensors)})
    return buf.getvalue()


def frames_from_bytes(blob: bytes) -> List[np.ndarray]:
    with np.load(io.BytesIO(blob), allow_pickle=False) as z:
        return [z[f"t{i}"] for i in range(len(z.files))]


def ship_over_socket(blob: bytes) -> bytes:
    """Round one blob through a real loopback socket pair (length-
    prefixed), a writer thread feeding the far end — the wire-path
    exercise for tests/CI and the `--disaggregate wire` loopback: the
    bytes genuinely leave and re-enter the process boundary machinery,
    so framing/CRC bugs surface here, not on a multi-host fleet."""
    a, b = socket.socketpair()
    try:
        def feed():
            with a:
                a.sendall(struct.pack("!Q", len(blob)))
                a.sendall(blob)

        t = threading.Thread(target=feed, daemon=True,
                             name="kv-ship-feeder")
        t.start()
        with b:
            need = struct.unpack("!Q", _read_exact(b, 8))[0]
            out = _read_exact(b, need)
        t.join(timeout=60)
        return out
    finally:
        for s in (a, b):
            try:
                s.close()
            except OSError:
                pass


def _read_exact(sock: socket.socket, n: int) -> bytes:
    # THE exact-read primitive is comm/dcn.py's (recv_into, no
    # flattening copy) — one implementation, reused lazily so importing
    # the ship codec never pulls the DCN runtime in
    from ..comm.dcn import _recv_exact
    return bytes(_recv_exact(sock, n))
