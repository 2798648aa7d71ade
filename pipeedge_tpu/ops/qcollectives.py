"""EQuARX-style quantized collectives for the intra-stage (ICI) plane.

The int8/int4 wire path used to stop at the DCN edge (`comm/wire.py`): every
intra-stage TP `psum` (two full-width allreduces per Megatron block,
`parallel/tensor.py`) and the sequence-parallel `all_gather`
(`parallel/spmd.py`) still moved exact-width activations over ICI. This
module pushes the wire-bits path inward, per "EQuARX: Efficient Quantized
AllReduce in XLA" (arxiv 2506.17615, PAPERS.md):

- `qpsum`: quantized allreduce = per-shard block-scaled int8/int4 encode ->
  ring reduce-scatter in quantized form with a WIDENED (f32) accumulator
  (each hop dequantizes, folds in the local chunk at full precision, and
  re-encodes only the payload that travels) -> quantized all-gather of the
  reduced chunks, each encoded ONCE. The chunk a device reduces stays exact
  f32 on that device; every remote chunk carries bounded quantization error
  (`qpsum_error_bound`).
- `qall_gather`: each shard is encoded once and forwarded n-1 hops; the
  local shard stays exact.

Both are shard_map-body functions over a named mesh axis, built purely on
`jax.lax.ppermute`: no psum_scatter/all_gather takes a custom reduction,
so the ring IS the implementation, exactly the fallback EQuARX describes
for pre-collective-quantization XLA.

Block scaling reuses the repo's own codec: a chunk reshaped to
[n_blocks, block] IS an outer-dim batch, so the block-scaled encode is
`fused_quant.encode_outerdim` — the Pallas-fused kernel when enabled, the
XLA ops otherwise, bit-identical either way. The optional Banner clamp
(`ops/clamp.py`) bounds each collective's quantization step under the
Laplace activation model — the per-collective error-budget knob
(docs/QUANT_COLLECTIVES.md).

Observability: collectives execute inside XLA, so per-execution host spans
are impossible; instead every qpsum/qall_gather call records its static
per-execution wire footprint in a trace-time tally. Drivers call
`record_collectives()` after a run to fold the tally into `collective`
telemetry spans (name `{kind}{bit}:{wire_bytes}`) and the pre-declared
`pipeedge_collective_bits_total{collective,bits}` counter —
`tools/trace_report.py` folds these into the per-stage bits-moved section
that separates ICI-collective traffic from DCN-edge traffic.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from .. import telemetry
from ..telemetry.metrics import REGISTRY
from . import clamp as clamp_ops
from . import fused_quant
from . import quant as quant_ops

# bitwidths a quantized collective accepts (0 = exact passthrough)
QCOLLECTIVE_BITS = (0, 4, 8)

# values per scale/shift pair: small enough that one outlier only poisons
# its own block, large enough that the f32 scale/shift metadata stays ~3%
# of the int8 payload
DEFAULT_BLOCK = 256

COLLECTIVE_BITS_TOTAL = REGISTRY.counter(
    "pipeedge_collective_bits_total",
    "wire bits moved by quantized intra-stage collectives, per collective "
    "kind and bitwidth (per-device ring traffic)")
# pre-declared label matrix (docs/OBSERVABILITY.md; pipelint PL501): the
# full kind x bitwidth domain renders before the first increment
for _kind in ("psum", "all_gather"):
    for _bit in (4, 8):
        COLLECTIVE_BITS_TOTAL.declare(collective=_kind, bits=str(_bit))


# -- trace-time wire-footprint tally -------------------------------------

# every qpsum/qall_gather CALL (i.e. traced site) appends one entry:
# {kind, bit, n_shards, wire_bytes, raw_bytes} where wire_bytes is what ONE
# device sends per execution of the site (all ring hops, packed words +
# scale/shift metadata) and raw_bytes is what the exact f32 ring equivalent
# would send — their ratio is the site's wire reduction
_TRACE_TALLY: List[Dict] = []


def reset_trace_tally() -> None:
    """Clear the tally (drivers call this before building a program)."""
    _TRACE_TALLY.clear()


def trace_tally() -> List[Dict]:
    """Snapshot of the traced collective sites since the last reset."""
    return [dict(t) for t in _TRACE_TALLY]


def _enc_bytes_per_chunk(chunk: int, block: int, bit: int) -> int:
    """Wire bytes of one block-scaled encoded chunk: packed words + the
    per-block f32 scale/shift pair."""
    n_blocks = chunk // block
    return n_blocks * (quant_ops.packed_words(block, bit) * 4 + 8)


def _tally(kind: str, bit: int, n_shards: int, hops: int, chunk: int,
           block: int) -> None:
    _TRACE_TALLY.append({
        "kind": kind, "bit": bit, "n_shards": n_shards,
        "wire_bytes": hops * _enc_bytes_per_chunk(chunk, block, bit),
        "raw_bytes": hops * chunk * 4,
    })


def record_collectives(executions: int = 1,
                       stage: Optional[int] = None) -> Dict:
    """Fold the trace tally into telemetry + /metrics after a run.

    For each traced collective site: one instant `collective` span named
    `{kind}{bit}:{wire_bytes}` (trace_report's bits-moved section parses
    the name) and `pipeedge_collective_bits_total` incremented by the
    site's per-execution wire bits x `executions` — the caller's estimate
    of how many times each traced site actually ran (e.g. microbatches x
    blocks for the SPMD pipeline). Returns a summary record benches embed.
    """
    now = time.monotonic_ns()
    wire_bits = 0
    raw_bits = 0
    for t in _TRACE_TALLY:
        site_bytes = t["wire_bytes"] * executions
        site_bits = site_bytes * 8
        wire_bits += site_bits
        raw_bits += t["raw_bytes"] * 8 * executions
        # instant span per site, name = {kind}{bit}:{run-total wire bytes}
        # — report.analyze_spans parses the name into the per-stage
        # bits-moved section (ICI-collective bytes vs DCN-edge time)
        telemetry.record("collective", f"{t['kind']}{t['bit']}:"
                         f"{site_bytes}", now, now, stage=stage)
        COLLECTIVE_BITS_TOTAL.inc(amount=site_bits,
                                  collective=t["kind"], bits=str(t["bit"]))
    return {"sites": len(_TRACE_TALLY), "executions": executions,
            "wire_bits_total": wire_bits, "raw_bits_total": raw_bits,
            "wire_reduction": (round(raw_bits / wire_bits, 3)
                               if wire_bits else None)}


# -- the collectives -----------------------------------------------------

def _check_bit(bit: int) -> None:
    if bit not in QCOLLECTIVE_BITS:
        raise ValueError(f"quantized collectives support bits "
                         f"{QCOLLECTIVE_BITS}, got {bit}")


def _block_encode(chunk: jax.Array, bit: int,
                  block: int) -> quant_ops.QuantizedTensor:
    """Block-scaled encode of a flat [m] chunk (m % block == 0): each
    `block`-value group gets its own scale/shift — a reshaped outer-dim
    batch through the fused/XLA dispatch seam."""
    return fused_quant.encode_outerdim(chunk.reshape(-1, block), bit)


def _block_decode(enc: quant_ops.QuantizedTensor) -> jax.Array:
    return fused_quant.decode_outerdim(enc).reshape(-1)


def _ring_fwd(tree, axis_name: str, n: int):
    perm = [(i, (i + 1) % n) for i in range(n)]
    return jax.tree_util.tree_map(
        lambda t: jax.lax.ppermute(t, axis_name, perm), tree)


def qpsum(x: jax.Array, axis_name: str, bit: int, *,
          block: int = DEFAULT_BLOCK, clamp: bool = False) -> jax.Array:
    """Quantized allreduce over a shard_map mesh axis (EQuARX-style).

    bit=0 is the exact `jax.lax.psum`. Otherwise: the flat tensor splits
    into n per-device chunks (zero-padded to n x block alignment); a ring
    reduce-scatter moves block-scaled int`bit` payloads with an f32
    accumulator (each hop: dequant, + local chunk, re-encode); a quantized
    ring all-gather then broadcasts each reduced chunk, encoded once.
    Result dtype follows `x`; internal accumulation is always f32 (wider
    than a bf16 psum — the EQuARX widened-accumulator contract).

    `clamp=True` applies the Banner Laplace clamp (`ops/clamp.py`) to the
    local addend first, trading bounded bias for a smaller quantization
    step — the per-collective error-budget knob. `qpsum_error_bound` gives
    the worst-case |quantized - exact| for the unclamped path.
    """
    _check_bit(bit)
    if bit == 0:
        return jax.lax.psum(x, axis_name)
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    if clamp:
        flat = clamp_ops.clamp_banner2019_laplace(flat, bit)
    m = flat.shape[0]
    chunk = block * (-(-m // (n * block)))
    total = n * chunk
    if total > m:
        flat = jnp.concatenate([flat, jnp.zeros((total - m,), jnp.float32)])
    chunks = flat.reshape(n, chunk)
    idx = jax.lax.axis_index(axis_name)

    def local(j):
        return jax.lax.dynamic_index_in_dim(chunks, j % n, axis=0,
                                            keepdims=False)

    # ring reduce-scatter, widened accumulator: at step s device i forwards
    # the partial sum of chunk (i - s) mod n and folds chunk (i - s - 1)
    # mod n of its own addend into what arrives
    send = local(idx)
    for s in range(n - 1):
        recv = _block_decode(_ring_fwd(_block_encode(send, bit, block),
                                       axis_name, n))
        send = recv + local(idx - s - 1)
    own = send                       # full sum of chunk (idx + 1) mod n

    # quantized all-gather of the reduced chunks: each encoded ONCE, so a
    # remote chunk carries exactly one quantization error and the locally
    # reduced chunk stays exact f32
    out = jnp.zeros((n, chunk), jnp.float32)
    own_pos = (idx + 1) % n

    def place(buf, piece, j):
        return jax.lax.dynamic_update_slice(buf, piece[None], (j % n, 0))

    out = place(out, own, own_pos)
    cur = _block_encode(own, bit, block)
    for k in range(1, n):
        cur = _ring_fwd(cur, axis_name, n)
        # after k hops this device holds the chunk reduced by (idx - k)
        out = place(out, _block_decode(cur), own_pos - k)
    _tally("psum", bit, n, 2 * (n - 1), chunk, block)
    return out.reshape(total)[:m].reshape(orig_shape).astype(orig_dtype)


def qall_gather(x: jax.Array, axis_name: str, bit: int, *, axis: int = 0,
                tiled: bool = True, block: int = DEFAULT_BLOCK,
                clamp: bool = False) -> jax.Array:
    """Quantized all-gather over a shard_map mesh axis.

    bit=0 is the exact `jax.lax.all_gather`. Otherwise each device
    block-scale-encodes its shard ONCE and the packed payload rides n-1
    ring hops; the local shard stays exact. `tiled=True` concatenates the
    shards along `axis` (the `jax.lax.all_gather(..., tiled=True)`
    contract the sequence-parallel pipeline uses); `tiled=False` stacks a
    new leading `axis` dimension.
    """
    _check_bit(bit)
    if bit == 0:
        return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x if tiled else jnp.expand_dims(x, axis)
    orig_dtype = x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    if clamp:
        flat = clamp_ops.clamp_banner2019_laplace(flat, bit)
    m = flat.shape[0]
    pad = block * (-(-m // block)) - m
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    idx = jax.lax.axis_index(axis_name)

    pieces = jnp.zeros((n,) + x.shape, jnp.float32)

    def place(buf, piece, j):
        return jax.lax.dynamic_update_slice(
            buf, piece[None], (j % n,) + (0,) * x.ndim)

    # the local shard enters exact (not its quantized roundtrip)
    pieces = place(pieces, x.astype(jnp.float32), idx)
    cur = _block_encode(flat, bit, block)
    for k in range(1, n):
        cur = _ring_fwd(cur, axis_name, n)
        piece = _block_decode(cur)[:m].reshape(x.shape)
        pieces = place(pieces, piece, idx - k)
    _tally("all_gather", bit, n, n - 1, m + pad, block)
    parts = [pieces[j].astype(orig_dtype) for j in range(n)]
    if tiled:
        return jnp.concatenate(parts, axis=axis)
    return jnp.stack(parts, axis=axis)


def qpsum_error_bound(shard_absrange: float, bit: int, n_shards: int,
                      block: int = DEFAULT_BLOCK) -> float:
    """Conservative worst-case |qpsum - psum| per element (unclamped).

    Each reduce-scatter hop s quantizes a partial sum of s+1 shard chunks
    whose per-block range is at most (s+1) x `shard_absrange`; the gather
    hop quantizes the full n-shard sum. A block-scaled encode's round-off
    is half a step = range / (2^bit - 1) / 2. Summing the n-1 RS hops and
    the single AG encode, then doubling for float round-off slack:

        2 * (sum_{s=1}^{n-1} s + n) * R / (2 (2^bit - 1))

    where R = `shard_absrange` (max - min of any one shard's block).
    """
    del block  # the bound holds per block; range is the caller's worst block
    levels = float((1 << bit) - 1)
    hops = sum(range(1, n_shards)) + n_shards
    return 2.0 * hops * shard_absrange / (2.0 * levels) + 1e-5
