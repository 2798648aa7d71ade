"""Observability plane: span ring, clock alignment, merged Chrome trace,
Prometheus metrics registry, and the bubble/latency report math
(pipeedge_tpu/telemetry + tools/trace_report.py).

The fleet test at the bottom drives the acceptance path end to end: a
loopback 2-rank DCN round with `--trace-spans` must yield ONE merged
Perfetto-loadable trace covering all ranks with microbatch flow events,
and `tools/trace_report.py` must report bubble %, per-edge wire share, and
per-microbatch percentiles off that artifact with sub-1% recording
overhead.
"""
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pipeedge_tpu import telemetry
from pipeedge_tpu.comm import dcn
from pipeedge_tpu.telemetry import chrome_trace, metrics, report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_global_recorder():
    """Tests configure the module recorder; never leak it across tests."""
    yield
    telemetry.disable()


# -- span ring ----------------------------------------------------------

def test_ring_overflow_drops_oldest_never_blocks():
    rec = telemetry.SpanRecorder(rank=3, capacity=8)
    for i in range(20):
        rec.record("stage", f"s{i}", i * 10, i * 10 + 5, mb=i)
    assert len(rec) == 8
    assert rec.dropped == 12
    spans = rec.snapshot()
    # drop-oldest: only the 8 most recent survive, in order
    assert [s["mb"] for s in spans] == list(range(12, 20))
    assert all(s["rank"] == 3 for s in spans)

    # concurrent recording against snapshot/drain must neither block nor
    # corrupt the ring (the send-thread guarantee)
    stop = threading.Event()
    def hammer():
        i = 0
        while not stop.is_set():
            with rec.span("wire", "send", mb=i):
                pass
            i += 1
    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    t0 = time.monotonic()
    for _ in range(50):
        rec.snapshot()
        rec.drain()
    stop.set()
    for t in threads:
        t.join()
    assert time.monotonic() - t0 < 5.0
    assert len(rec.snapshot()) <= 8


def test_span_context_manager_and_disabled_fast_path():
    assert not telemetry.enabled()
    with telemetry.span("stage", "noop"):   # disabled: shared no-op
        pass
    rec = telemetry.configure(rank=1, capacity=16)
    with telemetry.span("stage", "работа", stage=2, mb=7):
        time.sleep(0.001)
    (s,) = rec.snapshot()
    assert s["cat"] == "stage" and s["stage"] == 2 and s["mb"] == 7
    assert s["t1"] - s["t0"] >= 1_000_000  # the 1 ms sleep
    assert s["rank"] == 1


def test_spans_wire_roundtrip():
    rec = telemetry.SpanRecorder(rank=2, capacity=4)
    rec.record("wire", "send->r1", 100, 200, mb=0)
    rec.record("compute", "stage0", 150, 300, stage=0, mb=1)
    spans = rec.snapshot()
    arr = telemetry.spans_to_wire(spans)
    assert arr.dtype == np.uint8
    assert telemetry.spans_from_wire(arr) == spans
    assert telemetry.spans_from_wire(np.zeros(0, np.uint8)) == []


# -- clock alignment ----------------------------------------------------

def test_clock_offset_recovers_known_skew():
    """Symmetric-RTT synthetic peers: the NTP estimate recovers the skew
    within tolerance regardless of (symmetric) network-delay noise."""
    rng = np.random.default_rng(0)
    skew = 123_456_789_000          # peer clock runs 123.5 ms ahead
    samples = []
    for _ in range(8):
        t0 = int(rng.integers(1e9, 2e9))
        d = int(rng.integers(50_000, 5_000_000))    # one-way transit
        proc = int(rng.integers(1_000, 50_000))
        t1 = t0 + d + skew
        t2 = t1 + proc
        t3 = t0 + d + proc + d
        samples.append((t0, t1, t2, t3))
    est = telemetry.estimate_clock_offset(samples)
    assert abs(est - skew) < 1_000   # sub-microsecond on symmetric paths
    # aligning a peer span lands it on the local timeline
    peer_span = {"cat": "stage", "name": "x", "rank": 1, "stage": None,
                 "mb": None, "t0": 1_000 + skew, "t1": 2_000 + skew}
    (aligned,) = telemetry.align_spans([peer_span], est)
    assert abs(aligned["t0"] - 1_000) < 1_000


def test_clock_offset_picks_min_rtt_sample():
    # the asymmetric-congestion sample would give a wrong answer; the
    # filter must prefer the clean (min-RTT) one
    clean = (1000, 2000, 2100, 3100)        # symmetric: offset 0
    congested = (1000, 2000, 2100, 60000)   # slow return path: offset
    # estimate would be badly negative if this sample were used
    assert telemetry.estimate_clock_offset([congested, clean]) == \
        telemetry.estimate_clock_offset([clean]) == 0


# -- merged chrome trace ------------------------------------------------

def _two_stage_spans():
    """Hand-built two-stage timeline: stages alternate perfectly (50%
    idle each), two microbatches, one wire hop."""
    ms = 1_000_000
    return [
        {"cat": "runtime", "name": "round0", "rank": 0, "stage": None,
         "mb": None, "t0": 0, "t1": 40 * ms},
        {"cat": "stage", "name": "stage0", "rank": 0, "stage": 0, "mb": 0,
         "t0": 0, "t1": 10 * ms},
        {"cat": "wire", "name": "send->r1", "rank": 0, "stage": None,
         "mb": None, "t0": 9 * ms, "t1": 10 * ms},
        {"cat": "stage", "name": "stage1", "rank": 1, "stage": 1, "mb": 0,
         "t0": 10 * ms, "t1": 20 * ms},
        {"cat": "stage", "name": "stage0", "rank": 0, "stage": 0, "mb": 1,
         "t0": 20 * ms, "t1": 30 * ms},
        {"cat": "stage", "name": "stage1", "rank": 1, "stage": 1, "mb": 1,
         "t0": 30 * ms, "t1": 40 * ms},
    ]


def test_chrome_trace_valid_and_deterministic(tmp_path):
    spans = _two_stage_spans()
    doc = chrome_trace.build_trace(spans)
    # deterministic for a fixed span set (CI artifact diffs rely on it)
    assert json.dumps(doc, sort_keys=True) == json.dumps(
        chrome_trace.build_trace(list(reversed(spans))), sort_keys=True)
    events = doc["traceEvents"]
    x = [e for e in events if e["ph"] == "X"]
    assert len(x) == len(spans)
    assert {e["pid"] for e in x} == {0, 1}          # one process per rank
    # one named track per (rank, category)
    names = {(e["pid"], e["args"]["name"]) for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert (0, "stage") in names and (1, "stage") in names \
        and (0, "wire") in names
    # microbatch flow events: start + finish per mb that crosses ranks
    flows = [e for e in events if e.get("cat") == "mb"]
    assert {e["name"] for e in flows} == {"mb0", "mb1"}
    assert len({e["id"] for e in flows}) == 2   # one flow id per group
    for mb in ("mb0", "mb1"):
        phases = [e["ph"] for e in flows if e["name"] == mb]
        assert phases[0] == "s" and phases[-1] == "f"
    # file round trip preserves the spans (what trace_report reads)
    path = tmp_path / "trace.json"
    chrome_trace.dump_trace(spans, str(path))
    back = chrome_trace.trace_to_spans(json.loads(path.read_text()))
    assert len(back) == len(spans)
    assert {(s["cat"], s["name"]) for s in back} == \
        {(s["cat"], s["name"]) for s in spans}


# -- report math --------------------------------------------------------

def test_report_bubble_math_two_stage_timeline():
    rep = report.analyze_spans(_two_stage_spans(), span_cost_ns=1000.0)
    assert rep["spans"] == 6
    assert rep["window_s"] == 0.04
    # each stage busy 20 of 40 ms -> 50% bubble
    assert rep["stages"]["stage0"]["busy_s"] == 0.02
    assert rep["stages"]["stage0"]["bubble_pct"] == 50.0
    assert rep["bubble_pct"] == 50.0
    # wire: 1 ms of 40 ms
    assert rep["edges"]["r0:send->r1"]["share_pct"] == 2.5
    # both microbatches take 20 ms end to end
    assert rep["mb_latency"]["n"] == 2
    assert rep["mb_latency"]["p50_ms"] == 20.0
    assert rep["mb_latency"]["p99_ms"] == 20.0
    # overhead: 6 spans x 1 us over 40 ms
    assert rep["span_overhead_pct"] == pytest.approx(0.015)


def test_report_failover_breakdown_and_empty():
    ms = 1_000_000
    spans = _two_stage_spans() + [
        {"cat": "failover", "name": "detect", "rank": 0, "stage": None,
         "mb": None, "t0": 12 * ms, "t1": 12 * ms},
        {"cat": "failover", "name": "reschedule", "rank": 0, "stage": None,
         "mb": None, "t0": 13 * ms, "t1": 15 * ms},
        {"cat": "failover", "name": "recover", "rank": 0, "stage": None,
         "mb": None, "t0": 12 * ms, "t1": 33 * ms},
    ]
    rep = report.analyze_spans(spans, span_cost_ns=1000.0)
    assert rep["failover"]["reschedule"] == 0.002
    assert rep["failover"]["detect_to_recover_s"] == 0.021
    assert rep["failover"]["recoveries_s"] == [0.021]
    assert report.analyze_spans([]) == {"spans": 0}


def test_report_rejoin_breakdown():
    """The elastic-membership section: admissions counted from instant
    'admit' spans; each 'heal' span's duration is that episode's
    time-to-full-capacity."""
    ms = 1_000_000
    spans = _two_stage_spans() + [
        {"cat": "rejoin", "name": "admit", "rank": 0, "stage": None,
         "mb": None, "t0": 20 * ms, "t1": 20 * ms},
        {"cat": "rejoin", "name": "heal", "rank": 0, "stage": None,
         "mb": None, "t0": 12 * ms, "t1": 37 * ms},
    ]
    rep = report.analyze_spans(spans, span_cost_ns=1000.0)
    assert rep["rejoin"]["admissions"] == 1
    assert rep["rejoin"]["heals"] == 1
    assert rep["rejoin"]["heals_s"] == [0.025]
    assert rep["rejoin"]["time_to_full_capacity_s"] == 0.025
    # no rejoin spans -> empty section (key present, falsy)
    assert report.analyze_spans(_two_stage_spans(),
                                span_cost_ns=1000.0)["rejoin"] == {}


def test_report_multi_failover_recoveries_are_per_event():
    """Two failovers far apart must NOT report the healthy time between
    them as recovery time — each recover span is its own event."""
    s = 1_000_000_000
    spans = [
        {"cat": "runtime", "name": "round0", "rank": 0, "stage": None,
         "mb": None, "t0": 0, "t1": 200 * s},
        {"cat": "failover", "name": "recover", "rank": 0, "stage": None,
         "mb": None, "t0": 10 * s, "t1": 11 * s},
        {"cat": "failover", "name": "recover", "rank": 0, "stage": None,
         "mb": None, "t0": 110 * s, "t1": 112 * s},
    ]
    rep = report.analyze_spans(spans, span_cost_ns=1000.0)
    assert rep["failover"]["recoveries_s"] == [1.0, 2.0]
    assert rep["failover"]["detect_to_recover_s"] == 2.0   # worst event


def test_mb_latency_segments_by_round():
    """mb ids restart each schedule round (re-schedule replays the same
    batch; --measure-rounds reruns it): latency must be per (round, mb),
    and flows must not chain across rounds."""
    ms = 1_000_000
    spans = []
    for rnd, base in ((0, 0), (1, 100 * ms)):
        spans.append({"cat": "runtime", "name": f"round{rnd}", "rank": 0,
                      "stage": None, "mb": None, "t0": base,
                      "t1": base + 20 * ms})
        for mb in (0, 1):
            spans.append({"cat": "stage", "name": "stage0", "rank": 0,
                          "stage": 0, "mb": mb, "t0": base + mb * 10 * ms,
                          "t1": base + mb * 10 * ms + 10 * ms})
    rep = report.analyze_spans(spans, span_cost_ns=1000.0)
    # 4 per-round microbatches of 10 ms each — NOT 2 of ~110 ms
    assert rep["mb_latency"]["n"] == 4
    assert rep["mb_latency"]["p99_ms"] == 10.0
    doc = chrome_trace.build_trace(spans)
    flows = [e for e in doc["traceEvents"] if e.get("cat") == "mb"]
    # 2 rounds x 2 mbs, each a distinct flow group (here single-hop
    # groups emit no arrows; ids would chain rounds if shared)
    assert len({e["id"] for e in flows}) == len({
        (e["id"], e["name"]) for e in flows})


# -- the report CLI on a synthetic trace ---------------------------------

def _report_cli(monkeypatch, capsys, *argv):
    """`tools/trace_report.py` in process: (exit code, printed record)."""
    from tools import trace_report
    monkeypatch.setattr(sys, "argv", ["trace_report.py", *argv])
    code = trace_report.main()
    out = capsys.readouterr().out.strip()
    return code, (json.loads(out.splitlines()[-1]) if out else None)


def _synthetic_input(tmp_path, shape, spans=None):
    """The two-stage timeline, its first microbatch tagged as request q1,
    written as either input shape the CLI takes."""
    spans = _two_stage_spans() if spans is None else spans
    spans = [dict(s, rid="q1") if s.get("mb") == 0 else s for s in spans]
    path = tmp_path / f"{shape}.json"
    if shape == "trace":
        chrome_trace.dump_trace(spans, str(path))
    else:
        path.write_text(json.dumps({
            "bundle": "pipeedge-postmortem", "trigger": "deadline",
            "rid": "q1", "spans": spans}))
    return str(path)


@pytest.mark.parametrize("shape", ["trace", "bundle"])
def test_trace_report_cli_reads_both_input_shapes(
        tmp_path, monkeypatch, capsys, shape):
    """A merged Chrome trace and a postmortem bundle give the same
    report: the bubble, the wire share and the microbatch percentiles of
    the timeline that was written, and `--require-spans` is satisfied."""
    path = _synthetic_input(tmp_path, shape)
    code, rec = _report_cli(monkeypatch, capsys, path, "--require-spans")
    assert code == 0 and rec["trace"] == path
    assert rec["spans"] == 6 and rec["bubble_pct"] == 50.0
    assert rec["edges"]["r0:send->r1"]["share_pct"] == 2.5
    assert rec["mb_latency"]["n"] == 2 and rec["mb_latency"]["p50_ms"] == 20.0
    assert rec["requests"]["n"] == 1
    assert rec["requests"]["worst"][0]["rid"] == "q1"


@pytest.mark.parametrize("shape,rid,code", [("trace", "q1", 0),
                                            ("bundle", "q1", 0),
                                            ("trace", "q9", 3)])
def test_trace_report_cli_request_timeline(tmp_path, monkeypatch, capsys,
                                           shape, rid, code):
    """`--request`: a known id prints its timeline (from a bundle, with
    what triggered the bundle) and exits 0; an unknown one exits 3."""
    path = _synthetic_input(tmp_path, shape)
    got, rec = _report_cli(monkeypatch, capsys, path, "--request", rid)
    assert got == code and rec["rid"] == rid and rec["found"] == (code == 0)
    if code == 0:
        assert rec["stages"] == [0, 1] and rec["total_ms"] == 20.0
        assert rec["dominant_stall"]["busy_ms"] == 10.0
        assert rec.get("bundle_trigger") == (
            "deadline" if shape == "bundle" else None)


@pytest.mark.parametrize("flag", ["--require-spans",
                                  "--require-local-edges"])
def test_trace_report_cli_gates_fail_on_a_trace_without_the_thing(
        tmp_path, monkeypatch, capsys, flag):
    """The machine-checkable gates: a trace with no microbatch spans
    fails `--require-spans`, one whose edges all rode the wire fails
    `--require-local-edges`, and the record is printed either way."""
    spans = ([s for s in _two_stage_spans() if s["cat"] != "stage"]
             if flag == "--require-spans" else None)
    path = _synthetic_input(tmp_path, "trace", spans)
    code, rec = _report_cli(monkeypatch, capsys, path, flag)
    assert code == 1 and rec["trace"] == path
    assert _report_cli(monkeypatch, capsys, path)[0] == 0


def test_trace_report_cli_wants_exactly_one_source(tmp_path, monkeypatch,
                                                   capsys):
    path = _synthetic_input(tmp_path, "trace")
    for argv in ((), (path, "--fleet", "http://127.0.0.1:1")):
        with pytest.raises(SystemExit) as err:
            _report_cli(monkeypatch, capsys, *argv)
        assert err.value.code == 2
        assert "exactly one" in capsys.readouterr().err


# -- percentile helpers --------------------------------------------------

@pytest.mark.parametrize("vals,q,want", [
    ([], 50, 0.0),                      # an empty series reports 0, no raise
    ([7.0], 99, 7.0),
    ([3.0, 1.0, 2.0], 50, 2.0),         # unsorted input
    ([1.0, 2.0, 3.0, 4.0], 0, 1.0),
    ([1.0, 2.0, 3.0, 4.0], 100, 4.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 3.0),    # nearest rank: round(1.5) = 2
    (list(range(1, 101)), 99, 99),
    (list(range(1, 101)), 95, 95),
], ids=["empty", "single", "unsorted", "q0", "q100", "even-n-median",
        "p99-of-100", "p95-of-100"])
def test_percentile_is_nearest_rank(vals, q, want):
    given = list(vals)
    assert report.percentile(vals, q) == want
    assert vals == given                    # sorted a copy, not the input
    if vals:
        # the ONE definition: the load generator's summary agrees
        from tools import loadgen
        assert loadgen._percentile(vals, q) == round(want, 3)


def test_segment_medians_percentiles_by_category():
    """`{cat/name: n, p50, p95}` over the categories asked for, open spans
    and other categories left out."""
    ms = 1_000_000
    spans = [{"cat": "stage", "name": "dispatch", "t0": 0, "t1": k * ms}
             for k in (1, 2, 3, 4, 100)]
    spans += [{"cat": "stage", "name": "dispatch", "t0": 0, "t1": None},
              {"cat": "wire", "name": "send->r1", "t0": 0, "t1": 5 * ms},
              {"cat": "serve", "name": "generate", "t0": 0, "t1": 9 * ms}]
    seg = report.segment_medians(spans)
    assert seg == {"stage/dispatch": {"n": 5, "p50_ms": 3.0, "p95_ms": 100.0},
                   "wire/send->": {"n": 1, "p50_ms": 5.0, "p95_ms": 5.0}}
    assert list(report.segment_medians(
        spans, cats=frozenset(("serve",)))) == ["serve/generate"]


# -- prometheus metrics -------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                 # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'          # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'     # more labels
    r" [-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|Inf|NaN)$")


def _assert_prometheus_text(text):
    """Every non-comment line must match the exposition format."""
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _PROM_LINE.match(line), f"bad prometheus line: {line!r}"


def test_metrics_registry_renders_prometheus_text():
    r = metrics.Registry()
    c = r.counter("edge_wire_bytes_total", "per-edge wire bytes")
    c.declare(edge="0->1")
    c.inc(4096, edge="1->2")
    g = r.gauge("edge_bits", "negotiated bitwidth")
    g.set(8, edge="0->1")
    h = r.histogram("request_latency_seconds", "latency")
    for v in (0.004, 0.03, 0.03, 7.0):
        h.observe(v)
    text = r.render()
    _assert_prometheus_text(text)
    assert 'edge_wire_bytes_total{edge="0->1"} 0' in text
    assert 'edge_wire_bytes_total{edge="1->2"} 4096' in text
    assert "# TYPE request_latency_seconds histogram" in text
    assert 'request_latency_seconds_bucket{le="0.005"} 1' in text
    assert 'request_latency_seconds_bucket{le="0.05"} 3' in text
    assert 'request_latency_seconds_bucket{le="+Inf"} 4' in text
    assert "request_latency_seconds_count 4" in text
    # idempotent declaration returns the same instrument
    assert r.counter("edge_wire_bytes_total", "x") is c
    with pytest.raises(ValueError):
        r.gauge("edge_wire_bytes_total", "wrong type")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_parse_exemplars_roundtrip():
    from pipeedge_tpu.telemetry import metrics as prom
    reg = prom.Registry()
    h = reg.histogram("bench_test_latency_seconds", "x",
                      buckets=(0.1, 1.0))
    h.observe(0.05, exemplar="q1")
    h.observe(0.5, exemplar="q2")
    h.observe(5.0, exemplar="q3")
    rows = prom.parse_exemplars(reg.render(),
                                "bench_test_latency_seconds")
    assert {(r["le"], r["trace_id"]) for r in rows} == {
        ("0.1", "q1"), ("1", "q2"), ("+Inf", "q3")}
    assert prom.parse_exemplars(reg.render(), "other_family") == []


def test_metrics_monitoring_snapshot_bridge(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)   # monitoring writes per-key CSVs in cwd
    import monitoring
    monitoring.init("shard", 4)
    try:
        monitoring.add_key("send", work_type="Mbits")
        monitoring.iteration_start("shard")
        monitoring.iteration("shard", work=8)
        snap = monitoring.snapshot()
        assert set(snap) == {"shard", "send"}
        assert snap["shard"]["global"]["work"] == 8
        assert snap["shard"]["instant"]["work"] == 8
        assert snap["shard"]["tag"] == 1
        assert snap["send"]["window"]["work"] == 0
        lines = metrics.render_monitoring_snapshot(snap)
        text = "\n".join(lines) + "\n"
        _assert_prometheus_text(text)
        assert 'pipeedge_monitor_work{key="shard",scope="global"} 8' in lines
    finally:
        monitoring.finish()
    assert monitoring.snapshot() == {}   # no session: empty, not an error


# -- fleet span collection over the command channel ---------------------

def _free_ports(n):
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def test_collect_spans_over_command_channel():
    """_MSG_SPANS: rank 0 pulls rank 1's ring + a clock offset; both live
    in this process, so the true offset is ~0 and the spans are shared."""
    rec = telemetry.configure(rank=1, capacity=64)
    rec.record("compute", "stage1", 1000, 2000, stage=1, mb=0)
    addrs = [("127.0.0.1", p) for p in _free_ports(2)]
    ctxs = [dcn.DistDcnContext(2, r, addrs) for r in range(2)]
    for c in ctxs:
        c.init()
    try:
        spans, offset = ctxs[0].collect_spans(1, probes=3, timeout=10.0)
        assert any(s["name"] == "stage1" and s["rank"] == 1 for s in spans)
        assert abs(offset) < 50_000_000   # same host, same clock: ~0
    finally:
        for c in ctxs:
            c.shutdown()


# -- acceptance path: traced loopback fleet + report --------------------

@pytest.mark.fleet
def test_traced_dcn_round_and_report(tmp_path):
    """A 2-rank loopback DCN round with --trace-spans produces one merged
    Perfetto-loadable trace covering all ranks with microbatch flow
    events; trace_report.py emits bubble/edge/latency fields off it, with
    span overhead under 1% (the ISSUE acceptance criteria)."""
    ports = _free_ports(2)
    addrs = ",".join(f"127.0.0.1:{p}" for p in ports)
    trace = tmp_path / "trace.json"
    common = [sys.executable, os.path.join(REPO, "runtime.py")]
    opts = ["-c", "dcn", "--platform", "cpu", "-m",
            "pipeedge/test-tiny-vit", "-pt", "1,4,5,8", "-q", "8,0",
            "-r", "0,1", "-b", "16", "-u", "4", "--dcn-addrs", addrs,
            "--sched-timeout", "120", "--trace-spans", str(trace)]
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               DCN_CONNECT_TIMEOUT="30")
    # the worker's log goes to a file: a pipe nobody reads while rank 0
    # runs holds 64 KB, and a worker that has logged that much blocks
    with open(tmp_path / "worker.log", "w") as log:
        worker = subprocess.Popen(common + ["1", "2"] + opts, cwd=tmp_path,
                                  env=env, stdout=log,
                                  stderr=subprocess.STDOUT, text=True)
    try:
        data = subprocess.run(common + ["0", "2"] + opts, cwd=tmp_path,
                              env=env, capture_output=True, text=True,
                              timeout=300)
    finally:
        try:
            worker.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            worker.kill()
    assert data.returncode == 0, data.stdout + data.stderr

    doc = json.loads(trace.read_text())
    x = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in x} == {0, 1}, "trace must cover all ranks"
    assert [e for e in doc["traceEvents"] if e.get("cat") == "mb"], \
        "microbatch flow events missing"

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(trace), "--require-spans"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    rep = json.loads(out.stdout)
    assert rep["spans"] > 0
    assert rep["bubble_pct"] is not None
    assert rep["edges"], "per-edge wire share missing"
    assert rep["mb_latency"]["n"] == 4     # 16/4 microbatches
    assert rep["mb_latency"]["p50_ms"] > 0
    assert rep["failover"] == {}           # clean run
    assert rep["span_overhead_pct"] < 1.0  # hot-path tax stays negligible


def test_per_round_bubble_skips_absent_stages():
    """A stage with no spans in a round (failed over away) is absent from
    that round's mean, not counted 100% idle."""
    ms = 1_000_000
    spans = []
    for rnd, base in ((0, 0), (1, 100 * ms)):
        spans.append({"cat": "runtime", "name": f"round{rnd}", "rank": 0,
                      "stage": None, "mb": None, "t0": base,
                      "t1": base + 20 * ms})
        stages = (0, 1, 2) if rnd == 0 else (0, 1)   # stage 2 died
        for st in stages:
            spans.append({"cat": "stage", "name": "dispatch", "rank": st,
                          "stage": st, "mb": 0, "t0": base,
                          "t1": base + 10 * ms})
    rep = report.analyze_spans(spans, span_cost_ns=1000.0)
    assert [r["bubble_pct"] for r in rep["rounds"]] == [50.0, 50.0]
