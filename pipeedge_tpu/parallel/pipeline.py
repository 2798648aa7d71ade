"""Host-driven pipeline driver: per-stage jitted programs, device_put edges.

This is the TPU equivalent of the reference's P2P pipeline
(/root/reference/src/pipeedge/comm/p2p/__init__.py:334-450): one "stage" per
device, microbatches streamed through the stages, results collected in FIFO
order. The reference needs four threads per rank (recv/work/send/command) and
a hand-rolled wire protocol because stages are separate Python processes
exchanging dynamically-shaped CPU tensors over TCP; under a single-controller
JAX program none of that machinery exists:

- A stage is a jit-compiled pure function resident on one device; its
  input/output signatures (shape/dtype/arity) are static per (model,
  partition, microbatch-size), so there is no framing protocol — the
  "wire format" is the compiled program signature (SURVEY.md §5.8).
- Dispatch is asynchronous: the host enqueues stage s for microbatch i and
  the transfer to stage s+1 without blocking, so while stage s computes
  microbatch i, stage s-1 computes microbatch i+1 — the same fill/drain
  overlap the reference builds with threads and maxsize-1 queues
  (p2p:88-93), but scheduled by the XLA runtime instead of Python locks.
- Backpressure (the reference's ConditionQueue semantics) is a bounded
  in-flight window: the host blocks on the oldest outstanding result once
  `max_inflight` microbatches are unfinished.

Quantized edges: each stage optionally decodes its input and encodes its
output (QuantPipe, reference runtime.py:73-119) *inside* the stage's jit, so
the pack/unpack fuses with the stage's first/last matmuls, and only the packed
uint32 payload crosses devices. Per-bitwidth compiled variants are cached —
bitwidth is compile-static (SURVEY.md §7 "hard parts").
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from .. import telemetry
from ..ops import clamp as clamp_ops
from ..ops import fused_quant
from ..ops import quant as quant_ops
from ..telemetry import metrics as prom

logger = logging.getLogger(__name__)

# Payload tuples use this transform on quantized edges. The reference clamps
# post-GeLU tensors with the gelu variant when the edge carries an MLP-up
# output (runtime.py:73-90); the hidden-state tensor uses the laplace variant.


def _encode_payload(payload, bit: int, clamp: bool):
    """Quantize every tensor in a stage-output payload (1- or 2-tuple)."""
    if bit == 0:
        return payload
    single = not isinstance(payload, tuple)
    tensors = (payload,) if single else payload
    out = []
    for t in tensors:
        if clamp:
            t = clamp_ops.clamp_banner2019_laplace(t, bit)
        # fused Pallas epilogue when enabled (ops/fused_quant.py): the
        # encode rides the stage's last matmul inside this same jit
        out.append(fused_quant.encode_outerdim(t, bit))
    return out[0] if single else tuple(out)


def _decode_payload(payload):
    """Dequantize a payload produced by `_encode_payload` (no-op otherwise);
    the fused-dequant consumer prologue when enabled."""
    if isinstance(payload, quant_ops.QuantizedTensor):
        return fused_quant.decode_outerdim(payload)
    if isinstance(payload, tuple) and any(
            isinstance(t, quant_ops.QuantizedTensor) for t in payload):
        return tuple(fused_quant.decode_outerdim(t) for t in payload)
    return payload


def _tunnel_decode_payload(payload):
    """Tunnel variant of `_decode_payload`: the payload's LEADING tensor
    stays an 8-bit `QuantizedTensor` — the stage's first sublayer leads
    with a dense that consumes the wire bytes directly in the int8 matmul
    (ops/int8_matmul.wire_dense), so the activation crosses the pipeline
    seam MXU-to-MXU without a dequant round-trip. Trailing tensors (the
    residual skip) decode normally; non-8-bit payloads fall back."""
    if isinstance(payload, quant_ops.QuantizedTensor):
        return payload if payload.bit == 8 else _decode_payload(payload)
    if isinstance(payload, tuple) and payload and isinstance(
            payload[0], quant_ops.QuantizedTensor) and payload[0].bit == 8:
        return (payload[0],) + tuple(
            _decode_payload(t) for t in payload[1:])
    return _decode_payload(payload)


@dataclasses.dataclass
class PipelineStage:
    """One pipeline stage: a shard function bound to a device.

    `quant_bit` applies to this stage's *output* edge (the reference registers
    the encode hook on the producing module, runtime.py:464-482). It may be
    changed between microbatches; each bitwidth compiles once and is cached.
    """
    shard_fn: Callable[[Dict, Any], Any]
    params: Dict
    device: jax.Device
    quant_bit: int = 0
    clamp: bool = True
    name: str = ""
    # Donate the (device_put-copied) payload buffers to XLA: the output
    # reuses the input's allocation instead of growing the arena each
    # microbatch. Only safe when the caller does not reuse the payload it
    # passes in — true for interior pipeline edges (each stage's input is
    # the previous stage's otherwise-unreferenced output), NOT for the
    # head stage, whose input is caller-owned (e.g. replayed across
    # --measure-rounds). build_pipeline sets it for stages > 0.
    donate_payload: bool = False
    # int8 stage-seam tunnel: leave the input payload's leading 8-bit
    # wire tensor ENCODED so this stage's first matmul eats it directly
    # (only set when the stage's first sublayer is wire-consuming —
    # FamilySpec.wire_subs — and the producing edge runs at 8 bits)
    tunnel: bool = False

    def __post_init__(self):
        with telemetry.startup("weights_place"):
            self.params = jax.device_put(self.params, self.device)
        self._compiled: Dict[int, Callable] = {}

    def _fn_for_bit(self, bit: int) -> Callable:
        fn = self._compiled.get(bit)
        if fn is None:
            shard_fn, do_clamp = self.shard_fn, self.clamp
            decode = _tunnel_decode_payload if self.tunnel \
                else _decode_payload

            def host_stage_step(params, payload):
                data = decode(payload)
                out = shard_fn(params, data)
                return _encode_payload(out, bit, do_clamp)

            with telemetry.startup("programs"):
                fn = jax.jit(host_stage_step, donate_argnums=(
                    (1,) if self.donate_payload else ()))
            self._compiled[bit] = fn
        return fn

    def __call__(self, payload):
        # tiered edge transfer (docs/DCN_WIRE.md): a payload already
        # resident on this stage's device (the single-device pipeline, or
        # consecutive stages sharing a chip) skips the device_put dispatch
        # entirely — the host-hop-free degenerate of the DCN colocated
        # hand-off; cross-device payloads ride device-to-device DMA/ICI.
        if not _payload_on_device(payload, self.device):
            with telemetry.span("wire", f"edge->{self.name or 'stage'}"):
                payload = jax.device_put(payload, self.device)
        return self._fn_for_bit(self.quant_bit)(self.params, payload)


class HostPipeline:
    """Drive microbatches through a chain of `PipelineStage`s.

    FIFO ordering is guaranteed (single dispatch thread + in-order device
    queues), which the reference could only promise for its P2P transport
    (rpc:44, runtime.py:250-254).
    """

    def __init__(self, stages: Sequence[PipelineStage], max_inflight: int = 0,
                 ubatch_callback: Optional[Callable[[int, Any], None]] = None,
                 edge_bytes_callback: Optional[
                     Callable[[int, List[int]], None]] = None):
        if not stages:
            raise ValueError("pipeline needs at least one stage")
        self.stages = list(stages)
        # Default window: 2 microbatches per stage (double buffering), the
        # analog of the reference's buffers_in=2/buffers_out=2 (sched model).
        self.max_inflight = max_inflight or 2 * len(self.stages)
        self.ubatch_callback = ubatch_callback
        # called at each microbatch's retirement with the per-edge wire byte
        # counts [stage0->1, stage1->2, ...] of that microbatch — the
        # single-controller analogue of the reference's per-rank send
        # monitoring hooks (p2p:132-152, runtime.py:219-230)
        self.edge_bytes_callback = edge_bytes_callback

    def enqueue(self, ubatch, edge_bytes: Optional[List[int]] = None,
                mb: Optional[int] = None,
                trace: Optional[telemetry.TraceContext] = None):
        """Dispatch one microbatch through all stages; returns the (device-
        resident, not yet materialized) final payload. When `edge_bytes` is a
        list, it receives the wire byte count of each inter-stage edge.
        `mb` tags the telemetry spans with the microbatch id (flow events
        on the merged trace); `trace` additionally tags them with the
        request id this microbatch serves (trace_report --request)."""
        data = ubatch
        last = len(self.stages) - 1
        rid = trace.rid if trace is not None else None
        for i, stage in enumerate(self.stages):
            # the span measures HOST dispatch time (device work is async;
            # the retire span is where device time surfaces) and, under a
            # live profiler session, names it on the trace's timeline
            with telemetry.span("stage", stage.name or f"stage{i}",
                                stage=i, mb=mb, rid=rid):
                data = stage(data)
            if edge_bytes is not None and i < last:
                edge_bytes.append(payload_wire_bytes(data))
        return _undequantized_guard(data)

    def run(self, ubatches: Sequence[Any],
            traces: Optional[Sequence[telemetry.TraceContext]] = None
            ) -> Tuple[List[Any], Dict[str, float]]:
        """Stream all microbatches; returns (results, stats). `traces`
        (optional, one per microbatch) request-tags each microbatch's
        dispatch/retire spans.

        Stats mirror the reference's end-of-run measurement: latency =
        t(last result) - t(first enqueue); throughput = total items / latency
        (reference runtime.py:493-505). `steady_state_throughput_items_sec`
        additionally excludes the FIRST microbatch — its latency carries
        the XLA compiles, and decisions fed by these stats (adaptive
        microbatching, benches) must not chase JIT noise.

        Retirement is opportunistic: after each dispatch, any already-
        finished microbatches at the head of the window retire without
        blocking, so a full window (or a slow result callback) only stalls
        dispatch when the oldest result genuinely isn't ready yet — not on
        every oldest microbatch's full host readback.
        """
        ubatches = list(ubatches)  # single pass: generators welcome
        results: List[Any] = []
        inflight: List[Any] = []
        # (items, t_retired) per microbatch, stamped as each result becomes
        # host-visible — the steady-state measurement's raw series
        retired: List[Tuple[int, float]] = []
        # per-mb end-to-end latency (enqueue -> host-visible result): the
        # fill/steady breakdown's raw series
        mb_latency_s: List[float] = []
        track_edges = self.edge_bytes_callback is not None
        tik = time.monotonic()
        dispatch_s: List[float] = []  # per-mb host enqueue cost (t_fixed)
        for i, ubatch in enumerate(ubatches):
            edge_bytes: Optional[List[int]] = [] if track_edges else None
            trace = traces[i] if traces is not None and i < len(traces) \
                else None
            t_d0 = time.monotonic()
            out = self.enqueue(ubatch, edge_bytes, mb=i, trace=trace)
            dispatch_s.append(time.monotonic() - t_d0)
            inflight.append((i, out, edge_bytes, t_d0, trace))
            while inflight and payload_ready(inflight[0][1]):
                self._retire(inflight.pop(0), results, retired, mb_latency_s)
            while len(inflight) >= self.max_inflight:
                self._retire(inflight.pop(0), results, retired, mb_latency_s)
        while inflight:
            self._retire(inflight.pop(0), results, retired, mb_latency_s)
        tok = time.monotonic()
        items = sum(_leading_dim(u) for u in ubatches)
        latency = tok - tik
        stats = {"latency_sec": latency,
                 "throughput_items_sec": items / latency if latency > 0 else 0.0,
                 "microbatches": len(ubatches),
                 # first dispatch carries the XLA compiles: average the rest
                 # when there is a rest (the planner's fixed-cost input)
                 "host_dispatch_s_per_ubatch":
                     (sum(dispatch_s[1:]) / (len(dispatch_s) - 1))
                     if len(dispatch_s) > 1
                     else (dispatch_s[0] if dispatch_s else 0.0)}
        if len(retired) >= 2:
            # window: first retirement -> last retirement, so the first
            # (compile-tainted) microbatch's latency is excluded while the
            # remaining M-1 retirements still measure the warm cadence
            steady_s = retired[-1][1] - retired[0][1]
            steady_items = sum(n for n, _ in retired[1:])
            if steady_s > 0:
                stats["steady_state_throughput_items_sec"] = \
                    steady_items / steady_s
                stats["steady_mb_interval_s"] = steady_s / (len(retired) - 1)
        if mb_latency_s:
            # fill vs steady split (BENCH latency-gap tracking, ROADMAP
            # item 5): the first microbatch's latency carries compile +
            # pipeline fill; the steady percentiles are what an SLO sees
            from pipeedge_tpu.telemetry.report import _percentile
            steady = sorted(mb_latency_s[1:]) or [mb_latency_s[0]]
            stats["latency_breakdown"] = {
                "fill_ms": round(mb_latency_s[0] * 1e3, 3),
                "steady_p50_ms": round(_percentile(steady, 50) * 1e3, 3),
                "steady_p99_ms": round(_percentile(steady, 99) * 1e3, 3),
            }
        return results, stats

    def _retire(self, item, results, retired: Optional[list] = None,
                mb_latency_s: Optional[list] = None):
        i, out, edge_bytes, t_enq, trace = item
        with telemetry.span("results", "retire", mb=i,
                            rid=trace.rid if trace is not None else None):
            out = jax.block_until_ready(out)
            # opt-in NaN/Inf guard (PIPEEDGE_NAN_GUARD=1): the host
            # driver's stage hand-offs stay on-device for overlap, so the
            # boundary check lands here, where the result is already
            # fenced — a poisoned microbatch raises the named error
            # instead of reaching the result callback
            from ..health import guard as nan_guard
            if nan_guard.nan_guard_enabled():
                out = nan_guard.check_finite(
                    out, where="host_pipeline/retire", mb=i,
                    rid=trace.rid if trace is not None else None)
        now = time.monotonic()
        if retired is not None:
            retired.append((_leading_dim(out), now))
        if mb_latency_s is not None and t_enq is not None:
            mb_latency_s.append(now - t_enq)
        if self.edge_bytes_callback is not None:
            self.edge_bytes_callback(i, edge_bytes)
        if self.ubatch_callback is not None:
            self.ubatch_callback(i, out)
        results.append(out)


def _leading_dim(ubatch) -> int:
    t = ubatch[0] if isinstance(ubatch, tuple) else ubatch
    return int(t.shape[0])


def _payload_on_device(payload, device) -> bool:
    """Whether every array in a stage payload is already committed to
    `device` (single-device shardings only). Conservative False for host
    arrays and anything that cannot answer, so callers fall back to the
    explicit device_put."""
    tensors = payload if isinstance(payload, tuple) else (payload,)
    for t in tensors:
        if isinstance(t, quant_ops.QuantizedTensor):
            if not _payload_on_device((t.data, t.scale, t.shift), device):
                return False
            continue
        sharding = getattr(t, "sharding", None)
        try:
            if sharding is None or sharding.device_set != {device}:
                return False
        except Exception:  # noqa: BLE001 - deleted buffer, odd sharding
            return False
    return True


def payload_ready(payload) -> bool:
    """Whether every array in a stage payload has finished computing
    (jax.Array.is_ready — no fence, no transfer). Conservative False for
    anything that cannot answer, so callers fall back to the blocking
    retirement path rather than fencing early."""
    tensors = payload if isinstance(payload, tuple) else (payload,)
    for t in tensors:
        is_ready = getattr(t, "is_ready", None)
        try:
            if is_ready is None or not is_ready():
                return False
        except Exception:  # noqa: BLE001 - deleted/donated buffer etc.
            return False
    return True


def plan_microbatches(n_items: int, n_stages: int, t_item_s: float,
                      t_fixed_s: float,
                      max_ubatch: Optional[int] = None) -> Tuple[int, int, float]:
    """Pick the microbatch size from MEASURED timings instead of a fixed
    `--ubatch`: minimize the modeled round latency

        T(M) = (M + S - 1) * (t_fixed + t_item * ceil(B/M))

    — the classic fill/drain tradeoff. More microbatches shrink the
    pipeline bubble ((S-1)/(M+S-1) of the round), fewer amortize the
    per-microbatch fixed overhead `t_fixed_s` (host dispatch, framing);
    `t_item_s` is the bottleneck stage's measured per-ITEM time. Returns
    `(ubatch_size, n_microbatches, predicted_latency_s)`; exhaustive over
    the distinct sizes (batches are small), deterministic."""
    if n_items < 1 or n_stages < 1:
        raise ValueError(f"need n_items >= 1 and n_stages >= 1, got "
                         f"{n_items}, {n_stages}")
    t_item = max(0.0, float(t_item_s))
    t_fixed = max(0.0, float(t_fixed_s))
    best = None
    seen = set()
    for m in range(1, n_items + 1):
        u = -(-n_items // m)
        if u in seen or (max_ubatch is not None and u > max_ubatch):
            continue
        seen.add(u)
        m_eff = -(-n_items // u)
        t = (m_eff + n_stages - 1) * (t_fixed + t_item * u)
        if best is None or t < best[2]:
            best = (u, m_eff, t)
    if best is None:
        raise ValueError(f"max_ubatch={max_ubatch} admits no microbatch "
                         f"size for {n_items} items")
    return best


def payload_wire_bytes(payload) -> int:
    """Bytes a stage-output payload puts on the inter-stage edge.

    For quantized payloads this counts the packed words plus scale/shift
    metadata (everything that actually travels, `QuantizedTensor.nbytes_wire`
    + per-item scalars); raw payloads count their array bytes. Shapes are
    known without materializing, so this never fences the device."""
    tensors = payload if isinstance(payload, tuple) else (payload,)
    total = 0
    for t in tensors:
        if isinstance(t, quant_ops.QuantizedTensor):
            total += t.nbytes_wire + t.scale.nbytes + t.shift.nbytes
        else:
            total += t.nbytes
    return total


def _undequantized_guard(data):
    """Final stage output must not leave the pipeline quantized."""
    if isinstance(data, quant_ops.QuantizedTensor) or (
            isinstance(data, tuple) and any(
                isinstance(t, quant_ops.QuantizedTensor) for t in data)):
        return _decode_payload(data)
    return data


def build_pipeline(model_name: str, partition: Sequence[Tuple[int, int]],
                   model_file: Optional[str] = None,
                   devices: Optional[Sequence[jax.Device]] = None,
                   quant_bits: Optional[Sequence[int]] = None,
                   dtype=None, max_inflight: int = 0) -> HostPipeline:
    """Build a host-driven pipeline from a model partition.

    `partition` is the reference's stage-layers list [[l0, r0], [l1, r1], ...]
    (runtime.py:291-355); `quant_bits[i]` quantizes the edge leaving stage i
    (reference `-q`, runtime.py:652-656). Stages are placed round-robin on
    `devices` (default: all local devices).

    Int8 tunnel: when the active `QuantizeCompute` config has `tunnel`
    set, a stage whose first sublayer leads with a dense
    (`FamilySpec.wire_subs`) and whose incoming edge runs at 8 bits keeps
    that payload encoded — its first matmul consumes the wire bytes
    directly (ops/int8_matmul.wire_dense).
    """
    import jax.numpy as jnp

    from ..models import registry
    from ..models.layers import quantize_compute

    prom.count_jax_compiles()
    if devices is None:
        devices = jax.local_devices()
    if dtype is None:
        dtype = jnp.float32
    if quant_bits is None:
        quant_bits = [0] * len(partition)
    wire_subs = getattr(
        registry.get_model_entry(model_name).family.FAMILY, "wire_subs", ())
    qc = quantize_compute()
    stages = []
    for i, (layer_start, layer_end) in enumerate(partition):
        fn, params, _ = registry.module_shard_factory(
            model_name, model_file, layer_start, layer_end, stage=i, dtype=dtype)
        dev = devices[i % len(devices)]
        bit = quant_bits[i] if i < len(quant_bits) else 0
        # final stage's output edge is the result path: never quantized
        if i == len(partition) - 1:
            bit = 0
        in_bit = quant_bits[i - 1] if 0 < i <= len(quant_bits) else 0
        tunnel = (qc.tunnel and i > 0 and in_bit == 8
                  and (layer_start - 1) % 4 in wire_subs)
        stages.append(PipelineStage(shard_fn=fn, params=params, device=dev,
                                    quant_bit=bit, name=f"stage{i}",
                                    donate_payload=i > 0, tunnel=tunnel))
    return HostPipeline(stages, max_inflight=max_inflight)
