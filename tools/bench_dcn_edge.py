"""DCN edge microbenchmark: wire-byte compression and overlap efficiency.

Measures the two claims of the overlapped int8 wire path (the DCN data-path
rebuild) so they are recorded, not asserted:

1. **Wire bytes per microbatch** — a ViT-Large-shaped activation
   (ubatch 8 x 197 x 1024, fp32) encoded as a v2 wire frame at bit 0 / 8 /
   4: activation-payload bytes (what replaces the raw fp32 tensors on the
   socket; exactly 32/bit smaller) and total frame bytes (payload + the
   O(ubatch) scale/shift/shape metadata — the number the transport monitor
   hooks see). Also pushes both frame kinds through a real loopback
   `DistDcnContext` edge and reports edge bytes/sec and frames/sec, so the
   byte reduction is visible as wall-clock transfer gain.

2. **Overlap efficiency** — steady-state microbatch latency of a loopback
   `DcnPipelineStage` in the pre-overlap configuration (single-phase
   `work_cb`, queue depth 1: compute, device->host readback and send
   serialize) vs the overlapped configuration (dispatch/readback split,
   depth 2: readback drains on the send thread while the next microbatch's
   compute dispatches). Phase costs are modeled with fixed sleeps
   (dispatch ~= readback), so the ideal speedup is ~2x and the measured
   number is the threading machinery's real overlap efficiency; the
   depth-1 split variant is reported alongside to separate the split's
   contribution from the buffering's.

3. **Transport-tier latency A/B** (`--latency`) — a loopback world-4
   fleet (data rank + one relay stage + two idle spares, the world-4
   shape a 1-stage schedule runs) streams ViT-shaped microbatches over
   each transport tier of docs/DCN_WIRE.md's selection matrix — legacy
   v2 socket, zero-copy socket (pooled recv), colocated hand-off — and
   reports, ONE JSON line per tier: individually-dispatched p50/p99
   end-to-end microbatch latency, the streamed steady-state ubatch time,
   and their ratio (the "10× gap" number of the former chip's record; the
   target is ratio ≤ 2 on the colocated path).

CPU-safe (JAX_PLATFORMS=cpu) — nothing here needs a TPU. Prints ONE JSON
line, BENCH-record style (one line per tier in --latency mode).

Usage: JAX_PLATFORMS=cpu python tools/bench_dcn_edge.py [--latency]
"""
import argparse
import json
import os
import queue
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pipeedge_tpu.telemetry.report import _percentile  # noqa: E402 - one
# percentile estimator across the latency benches and the span reports

UBATCH_SHAPE = (8, 197, 1024)   # ViT-Large hidden-state microbatch (b=8)
N_FRAMES = 8                    # loopback transfer reps per frame kind
N_UBATCH = 24                   # stage-overlap stream length
WORK_MS = 20.0                  # modeled dispatch (compute) cost
DRAIN_MS = 20.0                 # modeled readback (D2H + encode) cost


def _free_port() -> int:
    with socket.create_server(("127.0.0.1", 0)) as s:
        return s.getsockname()[1]


def bench_wire_bytes():
    """Frame sizes + loopback transfer rate for fp32 vs int8 vs 4-bit."""
    import jax.numpy as jnp

    from pipeedge_tpu.comm import dcn, wire

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=UBATCH_SHAPE).astype(np.float32))
    frames = {}
    for bit in (0, 8, 4):
        parts = wire.wire_encode_device(x, bit).finalize()
        frames[bit] = {
            "parts": parts,
            "payload_bytes": wire.frame_payload_bytes(parts),
            "total_bytes": wire.frame_wire_bytes(parts),
        }

    # real loopback edge: bytes/sec at each bitwidth
    ctx = dcn.DistDcnContext(1, 0, [("127.0.0.1", _free_port())])
    ctx.init()
    rates = {}
    try:
        for bit, f in frames.items():
            ctx.send_tensors(0, f["parts"])     # warm the self-connection
            ctx.recv_tensors(0, timeout=30)

            def feed(parts=f["parts"]):         # stream, don't ping-pong:
                for _ in range(N_FRAMES):       # throughput, not latency
                    ctx.send_tensors(0, parts)

            import threading
            feeder = threading.Thread(target=feed, daemon=True)
            tik = time.monotonic()
            feeder.start()
            got = 0
            for _ in range(N_FRAMES):
                got += wire.frame_wire_bytes(ctx.recv_tensors(0, timeout=30))
            dt = time.monotonic() - tik
            feeder.join()
            rates[bit] = {"mbytes_per_sec": round(got / dt / 1e6, 1),
                          "frames_per_sec": round(N_FRAMES / dt, 1)}
    finally:
        ctx.shutdown()

    fp32 = frames[0]
    out = {"fp32_payload_bytes_per_ubatch": fp32["payload_bytes"],
           "fp32_total_bytes_per_ubatch": fp32["total_bytes"]}
    for bit in (8, 4):
        f = frames[bit]
        out[f"int{bit}_payload_bytes_per_ubatch"] = f["payload_bytes"]
        out[f"int{bit}_total_bytes_per_ubatch"] = f["total_bytes"]
        out[f"int{bit}_payload_reduction"] = round(
            fp32["payload_bytes"] / f["payload_bytes"], 3)
        out[f"int{bit}_total_reduction"] = round(
            fp32["total_bytes"] / f["total_bytes"], 3)
    out["loopback_edge"] = {f"bit{b}": r for b, r in rates.items()}
    return out


def bench_overlap():
    """Steady-state ubatch latency: serialized (pre-overlap) vs overlapped."""
    from pipeedge_tpu.comm import dcn

    ctx = dcn.DistDcnContext(1, 0, [("127.0.0.1", _free_port())])
    ctx.init()

    def run(depth, split):
        results = queue.Queue()

        def dispatch(ts):
            time.sleep(WORK_MS / 1e3)
            return ts

        def readback(ts):
            time.sleep(DRAIN_MS / 1e3)
            return ts

        if split:
            stage = dcn.DcnPipelineStage(
                ctx, None, None, dispatch_cb=dispatch, readback_cb=readback,
                depth=depth, results_cb=results.put)
        else:       # the pre-overlap contract: both phases on one thread
            stage = dcn.DcnPipelineStage(
                ctx, None, None, work_cb=lambda ts: readback(dispatch(ts)),
                depth=depth, results_cb=results.put)
        stage.start()
        try:
            tik = time.monotonic()
            for i in range(N_UBATCH):
                stage.enqueue_tensors([np.full((1,), i, np.int32)])
            outs = [results.get(timeout=120) for _ in range(N_UBATCH)]
            dt = time.monotonic() - tik
        finally:
            stage.stop()
        assert [int(o[0][0]) for o in outs] == list(range(N_UBATCH)), \
            "FIFO order violated"
        return dt / N_UBATCH * 1e3

    try:
        serialized = run(depth=1, split=False)
        split_d1 = run(depth=1, split=True)
        overlapped = run(depth=2, split=True)
    finally:
        ctx.shutdown()
    return {
        "modeled_work_ms": WORK_MS,
        "modeled_drain_ms": DRAIN_MS,
        "depth1_serialized_ubatch_ms": round(serialized, 2),
        "depth1_split_ubatch_ms": round(split_d1, 2),
        "depth2_overlapped_ubatch_ms": round(overlapped, 2),
        # serialized costs work+drain per ubatch; perfect overlap costs
        # max(work, drain) — efficiency 1.0 means the full phase overlap
        # was realized by the dispatch/readback split + depth-2 buffering
        "overlap_speedup": round(serialized / overlapped, 3),
        "overlap_efficiency": round(
            (serialized - overlapped) /
            (serialized - max(WORK_MS, DRAIN_MS)), 3),
    }


# -- transport-tier latency A/B (--latency) ------------------------------

LAT_WORLD = 4                   # data rank + 1 relay stage + 2 idle spares
LAT_N_UBATCH = 24               # per-tier stream length
LAT_WORK_MS = 8.0               # modeled stage compute (ViT-L ubatch-ish,
#                                 8.15 ms on the former chip)

# tier name -> env staging applied BEFORE the fleet's contexts exist
# (both knobs are read at context construction)
LAT_TIERS = (
    ("socket_v2", {"DCN_LOCAL_HANDOFF": "0", "DCN_RECV_POOL": "0"}),
    ("zerocopy", {"DCN_LOCAL_HANDOFF": "0", "DCN_RECV_POOL": "1"}),
    ("local", {"DCN_LOCAL_HANDOFF": "1", "DCN_RECV_POOL": "1"}),
)


def _free_ports(n):
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports



def bench_latency_tier(tier: str, env: dict) -> dict:
    """One tier's loopback world-4 run: data rank 0 streams microbatches
    to a relay stage on rank 1 (modeled compute LAT_WORK_MS), results come
    home to rank 0; ranks 2-3 idle. Individually-dispatched latency and
    streamed steady-state cadence per the BENCH latency method."""
    from pipeedge_tpu.comm import dcn

    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        addrs = [("127.0.0.1", p) for p in _free_ports(LAT_WORLD)]
        ctxs = [dcn.DistDcnContext(LAT_WORLD, r, addrs)
                for r in range(LAT_WORLD)]
        for c in ctxs:
            c.init()
    finally:
        for k, v in saved.items():
            (os.environ.pop(k, None) if v is None
             else os.environ.__setitem__(k, v))
    rng = np.random.default_rng(0)
    payload = [rng.normal(size=UBATCH_SHAPE).astype(np.float32)]

    def work(tensors):
        time.sleep(LAT_WORK_MS / 1e3)
        return tensors

    stage = dcn.DcnPipelineStage(ctxs[1], rank_src=0, rank_dst=0,
                                 work_cb=work,
                                 send_channel=dcn.CHANNEL_RESULTS)
    stage.start()
    try:
        # negotiate both directions of the relay edge (producer-side, the
        # runtime's round-build idiom), then verify the tier we got
        ctxs[0].negotiate_edge_path(1, timeout=10)
        ctxs[1].negotiate_edge_path(0, timeout=10)
        got = ctxs[0].edge_path(1)
        # warm the edge (dials + first-frame costs stay out of the stats)
        ctxs[0].send_tensors(1, payload)
        ctxs[0].recv_tensors(1, timeout=30, channel=dcn.CHANNEL_RESULTS)

        # individually dispatched: enqueue -> result home, fenced per mb
        lats = []
        for _ in range(LAT_N_UBATCH):
            tik = time.monotonic()
            ctxs[0].send_tensors(1, payload)
            ctxs[0].recv_tensors(1, timeout=30,
                                 channel=dcn.CHANNEL_RESULTS)
            lats.append(time.monotonic() - tik)

        # streamed: feeder thread keeps the stage busy; cadence = T/M
        def feed():
            for _ in range(LAT_N_UBATCH):
                ctxs[0].send_tensors(1, payload)

        feeder = threading.Thread(target=feed, daemon=True)
        tik = time.monotonic()
        feeder.start()
        for _ in range(LAT_N_UBATCH):
            ctxs[0].recv_tensors(1, timeout=30,
                                 channel=dcn.CHANNEL_RESULTS)
        steady_s = (time.monotonic() - tik) / LAT_N_UBATCH
        feeder.join()
    finally:
        stage.stop()
        for c in ctxs:
            c.shutdown()
    lats_sorted = sorted(lats)
    p50 = _percentile(lats_sorted, 50)
    return {
        "metric": "dcn_transport_latency",
        "path": tier,
        "path_negotiated": got,
        "world": LAT_WORLD,
        "ubatch_shape": list(UBATCH_SHAPE),
        "modeled_work_ms": LAT_WORK_MS,
        "n_ubatch": LAT_N_UBATCH,
        "p50_microbatch_latency_ms": round(p50 * 1e3, 2),
        "p99_microbatch_latency_ms": round(
            _percentile(lats_sorted, 99) * 1e3, 2),
        "steady_state_ubatch_ms": round(steady_s * 1e3, 2),
        # the ROADMAP item 5 headline: end-to-end p50 over steady cadence
        # (1.0 = transport adds nothing; the former chip measured ~10)
        "p50_over_steady": round(p50 / steady_s, 3) if steady_s else None,
        "throughput_frames_sec": round(1.0 / steady_s, 1) if steady_s
        else None,
    }


def bench_latency() -> int:
    """A/B all three tiers; one JSON line per tier (oldest tier first so
    the gap reads top-to-bottom)."""
    for tier, env in LAT_TIERS:
        print(json.dumps(bench_latency_tier(tier, env)), flush=True)
    return 0


# -- quantized ICI collectives A/B (--quant-collectives) ------------------

def bench_quant_collectives() -> dict:
    """Collective-level A/B for the quantized ICI plane
    (docs/QUANT_COLLECTIVES.md): exact `psum`/`all_gather` vs the
    EQuARX-style `qpsum`/`qall_gather` over a 2-device mesh on
    ViT-Large-shaped activations — per-collective max-abs error against
    the analytic bound, wire-byte reduction from the trace tally, and
    loopback wall time per call (CPU numbers measure the codec overhead
    only; the wire win is ICI-bound and shows on TPU meshes)."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from pipeedge_tpu.ops import qcollectives
    from pipeedge_tpu.utils import jax_compat

    devs = jax.devices()
    assert len(devs) >= 2, "main() forces a >=2-device host mesh"
    mesh = Mesh(np.asarray(devs[:2]), ("tp",))
    rng = np.random.default_rng(0)
    # two per-device psum addends, ViT-L row-parallel-output-shaped
    x = jnp.asarray(rng.normal(size=(2,) + UBATCH_SHAPE).astype(np.float32))
    exact_sum = np.asarray(x).sum(axis=0)
    shard_absrange = float(max(
        np.asarray(x)[i].max() - np.asarray(x)[i].min() for i in range(2)))

    out = {"metric": "quant_collectives_ici", "world": 2,
           "ubatch_shape": list(UBATCH_SHAPE)}
    for bit in (8, 4):
        qcollectives.reset_trace_tally()
        # offline bench: one jit per benched bitwidth, never a hot path
        fn = jax.jit(jax_compat.shard_map(  # pipelint: disable=PL301
            partial(qcollectives.qpsum, axis_name="tp", bit=bit),
            mesh=mesh, in_specs=P("tp"), out_specs=P("tp")))
        got = np.asarray(fn(x))            # compile + correctness sample
        err = float(np.abs(got - exact_sum[None]).max())
        bound = qcollectives.qpsum_error_bound(shard_absrange, bit, 2)
        reps = []
        for _ in range(N_FRAMES):
            tik = time.monotonic()
            np.asarray(fn(x))
            reps.append(time.monotonic() - tik)
        tally = qcollectives.trace_tally()[0]
        out[f"qpsum_int{bit}"] = {
            "max_abs_error": round(err, 6),
            "error_bound": round(bound, 6),
            "within_bound": err <= bound,
            "wire_bytes_per_device": tally["wire_bytes"],
            "raw_bytes_per_device": tally["raw_bytes"],
            "wire_reduction": round(
                tally["raw_bytes"] / tally["wire_bytes"], 3),
            "loopback_ms_per_call": round(
                sorted(reps)[len(reps) // 2] * 1e3, 2),
        }
    # exact psum reference timing (same mesh, same loopback)
    fn0 = jax.jit(jax_compat.shard_map(
        lambda t: jax.lax.psum(t, "tp"), mesh=mesh,
        in_specs=P("tp"), out_specs=P("tp")))
    np.asarray(fn0(x))
    reps = []
    for _ in range(N_FRAMES):
        tik = time.monotonic()
        np.asarray(fn0(x))
        reps.append(time.monotonic() - tik)
    out["exact_psum"] = {"loopback_ms_per_call": round(
        sorted(reps)[len(reps) // 2] * 1e3, 2)}
    out["value"] = out["qpsum_int8"]["wire_reduction"]
    out["unit"] = "x fewer ICI collective wire bytes at int8 vs fp32"
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--latency", action="store_true",
                   help="run the transport-tier latency A/B (one JSON "
                        "line per tier) instead of the wire/overlap bench")
    p.add_argument("--quant-collectives", action="store_true",
                   help="run the quantized ICI collectives A/B "
                        "(qpsum/qall_gather vs exact, one JSON line; "
                        "docs/QUANT_COLLECTIVES.md)")
    args = p.parse_args()
    if args.quant_collectives:
        # a >= 2-device mesh even on CPU-only hosts: force BEFORE the
        # first jax backend init (parse-once flag). This bench measures
        # codec numerics + bytes, so the virtual-CPU mesh is the point —
        # same idiom as the test suite's 8-device conftest.
        from pipeedge_tpu.utils import force_host_cpu_devices
        force_host_cpu_devices(2)
        print(json.dumps(bench_quant_collectives()))
        return
    if args.latency:
        sys.exit(bench_latency())
    record = {"metric": "dcn_edge_wire_and_overlap",
              "ubatch_shape": list(UBATCH_SHAPE)}
    record.update(bench_wire_bytes())
    record["overlap"] = bench_overlap()
    # headline: the two acceptance numbers
    record["value"] = record["int8_payload_reduction"]
    record["unit"] = "x fewer activation wire bytes at int8 vs fp32"
    print(json.dumps(record))


if __name__ == "__main__":
    main()
