"""Request-scoped distributed tracing (docs/OBSERVABILITY.md, ISSUE 10):
trace-context wire round-trip (present / absent / truncated), span-ring
rid tagging, metric exemplars under window rollover, the flight recorder's
postmortem bundles, the per-request causal timeline with dominant-stall
attribution, and the tier-1 loopback acceptance run — one artificially
delayed request whose `trace_report --request` names the injected stall.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pipeedge_tpu import telemetry
from pipeedge_tpu.comm import dcn
from pipeedge_tpu.telemetry import chrome_trace, flight, report
from pipeedge_tpu.telemetry import metrics as prom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- trace context -------------------------------------------------------

def test_trace_context_wire_roundtrip():
    ctx = telemetry.TraceContext("q17", "batch", deadline_ms=1500.0,
                                 parent="serve.generate")
    back = telemetry.TraceContext.from_wire(ctx.to_wire())
    assert back.rid == "q17" and back.cls == "batch"
    assert back.deadline_ms == 1500.0 and back.parent == "serve.generate"
    # optional fields stay optional
    lean = telemetry.TraceContext.from_wire(
        telemetry.TraceContext("q0").to_wire())
    assert lean.rid == "q0" and lean.deadline_ms is None


def test_trace_context_absent_and_truncated_decode_to_none():
    """The tolerance contract: absent, truncated, or garbage blobs mean
    UNTRACED — never an exception into the reader thread."""
    blob = telemetry.TraceContext("q1").to_wire()
    assert telemetry.TraceContext.from_wire(blob[: len(blob) // 2]) is None
    assert telemetry.TraceContext.from_wire(np.zeros(0, np.uint8)) is None
    junk = np.frombuffer(b'{"cls": "no-rid-here"}', np.uint8)
    assert telemetry.TraceContext.from_wire(junk) is None
    not_json = np.frombuffer(b"\xff\xfe\x00garbage", np.uint8)
    assert telemetry.TraceContext.from_wire(not_json) is None


def test_trace_scope_thread_local_and_restores():
    outer = telemetry.TraceContext("outer")
    inner = telemetry.TraceContext("inner")
    telemetry.set_trace(None)
    with telemetry.trace_scope(outer):
        assert telemetry.current_trace().rid == "outer"
        with telemetry.trace_scope(inner):
            assert telemetry.current_trace().rid == "inner"
        assert telemetry.current_trace().rid == "outer"
        seen = []

        def other_thread():
            seen.append(telemetry.current_trace())

        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
        assert seen == [None]     # TLS: never leaks across threads
    assert telemetry.current_trace() is None


def test_span_ring_rid_tagging_and_wire_compat():
    rec = telemetry.SpanRecorder(rank=1, capacity=8)
    rec.record("stage", "dispatch", 10, 20, stage=0, mb=3, rid="q5")
    rec.record("wire", "send->r1", 30, 40)          # untraced
    s_tagged, s_plain = rec.snapshot()
    assert s_tagged["rid"] == "q5" and s_plain["rid"] is None
    # implicit tagging from the thread's current trace context
    with telemetry.trace_scope(telemetry.TraceContext("q9")):
        rec.record("compute", "stage1", 50, 60, stage=1)
    assert rec.snapshot()[-1]["rid"] == "q9"
    # wire codec: rid survives, and PRE-tracing 7-field rows (a peer on
    # an older build) still decode — rid simply absent
    spans = rec.snapshot()
    assert telemetry.spans_from_wire(telemetry.spans_to_wire(spans)) \
        == spans
    old_row = json.dumps([["stage", "emit", 0, 1, 2, 100, 200]]).encode()
    (decoded,) = telemetry.spans_from_wire(
        np.frombuffer(old_row, np.uint8))
    assert decoded["name"] == "emit" and "rid" not in decoded


def test_chrome_trace_rid_roundtrip():
    spans = [{"cat": "stage", "name": "exec0", "rank": 0, "stage": 0,
              "mb": 1, "rid": "q3", "t0": 1000, "t1": 2000},
             {"cat": "wire", "name": "send->r1", "rank": 0, "stage": None,
              "mb": None, "rid": None, "t0": 1500, "t1": 1800}]
    doc = chrome_trace.build_trace(spans)
    back = chrome_trace.trace_to_spans(doc)
    by_name = {s["name"]: s for s in back}
    assert by_name["exec0"]["rid"] == "q3"
    assert by_name["send->r1"]["rid"] is None


# -- wire: traced frames -------------------------------------------------

def _free_ports(n):
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _make_contexts(n):
    ports = _free_ports(n)
    addrs = [("127.0.0.1", p) for p in ports]
    ctxs = [dcn.DistDcnContext(n, r, addrs) for r in range(n)]
    for c in ctxs:
        c.init()
    return ctxs


def test_dcn_traced_frame_roundtrip_present_absent_truncated():
    """A traced frame delivers its context; a plain frame delivers None;
    a truncated/garbage blob delivers the payload UNTRACED and bumps the
    invalid counter — the reader thread survives all three."""
    ctxs = _make_contexts(2)
    try:
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        tctx = telemetry.TraceContext("req-a", "interactive",
                                      deadline_ms=900.0)
        ctxs[0].send_tensors(1, [x], trace=tctx)
        got, _, back = ctxs[1].recv_tensors_traced(0, timeout=10)
        np.testing.assert_array_equal(got[0], x)
        assert back.rid == "req-a" and back.deadline_ms == 900.0
        assert dcn._TRACED_FRAMES.value(peer="0") >= 1

        ctxs[0].send_tensors(1, [x])                   # absent = untraced
        got, _, back = ctxs[1].recv_tensors_traced(0, timeout=10)
        np.testing.assert_array_equal(got[0], x)
        assert back is None

        # hand-build a traced frame whose blob is garbage: payload still
        # delivered, context None, invalid counter bumped
        invalid_before = dcn._TRACE_INVALID.value()
        with ctxs[0]._conn_locks[1]:
            conn = ctxs[0]._ensure_conn(1)
            dcn._send_frame(conn, dcn._MSG_TENSORS_TRACED, 0,
                            [np.frombuffer(b'{"truncated',
                                           np.uint8), x])
        got, _, back = ctxs[1].recv_tensors_traced(0, timeout=10)
        np.testing.assert_array_equal(got[0], x)
        assert back is None
        assert dcn._TRACE_INVALID.value() == invalid_before + 1
    finally:
        for c in ctxs:
            c.shutdown()


def test_dcn_stage_spans_inherit_request_id():
    """A DcnPipelineStage's dispatch/emit spans carry the inbound frame's
    request id, and the context propagates DOWNSTREAM automatically —
    rank 1's stage emits to rank 0 and the results frame still carries
    the feed's rid (the fleet-wide inheritance the tentpole requires)."""
    rec = telemetry.configure(rank=0)
    ctxs = _make_contexts(2)
    try:
        done = threading.Event()
        results = []

        def work(tensors):
            return [tensors[0] * 2]

        stage = dcn.DcnPipelineStage(ctxs[1], rank_src=0, rank_dst=0,
                                     work_cb=work, stage=1)
        with stage:
            tctx = telemetry.TraceContext("r0.mb0", "batch")
            ctxs[0].send_tensors(1, [np.ones(4, np.float32)], trace=tctx)
            got, _, back = ctxs[0].recv_tensors_traced(1, timeout=10)
            results.append(got)
            assert back is not None and back.rid == "r0.mb0"
            done.set()
        assert done.is_set()
        np.testing.assert_array_equal(results[0][0],
                                      2 * np.ones(4, np.float32))
        rids = {(s["cat"], s["name"]): s["rid"] for s in rec.snapshot()}
        assert rids.get(("stage", "dispatch")) == "r0.mb0"
        assert rids.get(("stage", "emit")) == "r0.mb0"
    finally:
        telemetry.disable()
        for c in ctxs:
            c.shutdown()


# -- metric exemplars ----------------------------------------------------

def test_exemplar_retains_max_latency_trace_id():
    h = prom.Histogram("t_req_latency", "t", buckets=(0.1, 1.0),
                       exemplar_window_s=60.0)
    # near the real clock so render() (which reads time.monotonic for
    # window pruning) sees the exemplars as fresh
    t = time.monotonic()
    h.observe(0.5, exemplar="q1", now=t - 3.0)
    h.observe(0.9, exemplar="q2", now=t - 2.0)  # same bucket, worse: wins
    h.observe(0.6, exemplar="q3", now=t - 1.0)  # not worse: ignored
    h.observe(5.0, exemplar="q4", now=t)        # +Inf overflow bucket
    ex = h.exemplars(now=t)
    assert ex["1"]["trace_id"] == "q2" and ex["1"]["value"] == 0.9
    assert ex["+Inf"]["trace_id"] == "q4"
    # exemplar comment lines ride /metrics without breaking the text
    # format (every '#' line that is not HELP/TYPE is parser-skipped)
    lines = h.render()
    ex_lines = [ln for ln in lines if ln.startswith("# EXEMPLAR")]
    assert any('trace_id="q2"' in ln and 'le="1"' in ln
               for ln in ex_lines)
    for ln in lines:
        assert ln.startswith("#") or ln.split()[1].replace(".", "").isdigit()


def test_exemplar_window_rollover_admits_fresh_observation():
    """After the window rolls over, a SMALLER fresh observation replaces
    the stale maximum — the 'per bucket window' retention semantics."""
    h = prom.Histogram("t_roll", "t", buckets=(1.0,),
                       exemplar_window_s=10.0)
    h.observe(0.9, exemplar="old-max", now=0.0)
    h.observe(0.2, exemplar="mid", now=5.0)          # within window: loses
    assert h.exemplars(now=5.0)["1"]["trace_id"] == "old-max"
    h.observe(0.1, exemplar="fresh", now=20.0)       # rolled over: wins
    assert h.exemplars(now=20.0)["1"]["trace_id"] == "fresh"
    # an expired exemplar with no successor disappears rather than lie
    h2 = prom.Histogram("t_expire", "t", buckets=(1.0,),
                        exemplar_window_s=10.0)
    h2.observe(0.5, exemplar="only", now=0.0)
    assert h2.exemplars(now=5.0) and not h2.exemplars(now=30.0)


# -- flight recorder -----------------------------------------------------

def test_flight_recorder_ring_dump_and_cooldown(tmp_path):
    fr = flight.FlightRecorder(rank=3, capacity=4,
                               out_dir=str(tmp_path), cooldown_s=60.0)
    for i in range(6):                       # overflow: drop-oldest
        fr.note("admit", rid=f"q{i}", cls="interactive")
    assert fr.dropped == 2
    assert [e["rid"] for e in fr.events()] == ["q2", "q3", "q4", "q5"]
    assert [e["rid"] for e in fr.events(rid="q4")] == ["q4"]

    before = fr.written_total()
    path = fr.maybe_dump("deadline", rid="q4",
                         context={"admission": {"queue_depth": 2}})
    assert path is not None and os.path.exists(path)
    assert fr.last_path() == path
    assert fr.written_total() == before + 1
    bundle = json.load(open(path))
    assert bundle["bundle"] == "pipeedge-postmortem"
    assert bundle["trigger"] == "deadline" and bundle["rid"] == "q4"
    assert bundle["rank"] == 3
    assert bundle["context"]["admission"]["queue_depth"] == 2
    assert any(e["rid"] == "q4" for e in bundle["events"])

    # cooldown: a second deadline dump inside the window is suppressed...
    assert not fr.would_dump("deadline")      # the cheap pre-check agrees
    assert fr.would_dump("manual")            # manual is never suppressed
    assert fr.maybe_dump("deadline", rid="q5") is None
    # ...other triggers have their own clocks, manual is never suppressed
    assert fr.maybe_dump("failover") is not None
    assert fr.maybe_dump("manual") is not None
    assert fr.maybe_dump("manual") is not None
    with pytest.raises(ValueError):
        fr.maybe_dump("nonsense")


def test_flight_bundle_carries_request_span_slice(tmp_path):
    """With span recording on, a bundle's `spans` slice holds the rid's
    spans plus mb-linked neighbors — and trace_report's loader consumes a
    bundle directly."""
    rec = telemetry.configure(rank=0)
    try:
        rec.record("serve", "admit:interactive", 0, 10, rid="q7")
        rec.record("stage", "exec0", 10, 50, stage=0, mb=0, rid="q7")
        rec.record("wire", "send->r1", 20, 30, mb=0)      # mb-linked
        rec.record("stage", "exec0", 60, 70, stage=0, mb=9, rid="other")
        fr = flight.FlightRecorder(out_dir=str(tmp_path))
        path = fr.maybe_dump("manual", rid="q7")
        bundle = json.load(open(path))
        names = {s["name"] for s in bundle["spans"]}
        assert names == {"admit:interactive", "exec0", "send->r1"}
        assert all(s["rid"] == "q7" or s["mb"] == 0
                   for s in bundle["spans"])
        tl = report.request_timeline(bundle["spans"], "q7")
        assert tl["found"] and tl["dominant_stall"]["segment"] \
            == "stage0/compute"
    finally:
        telemetry.disable()


def test_flight_trace_slice_none_keeps_all():
    spans = [{"rid": "a", "mb": 1, "t0": 0, "t1": 1},
             {"rid": None, "mb": 2, "t0": 1, "t1": 2}]
    assert len(flight.trace_slice(spans, None)) == 2


# -- request timeline ----------------------------------------------------

def _ms(n):
    return n * 1_000_000


def test_request_timeline_dominant_stall_and_attribution():
    """Hand-built two-rank request: queue wait 2ms, stage0 compute 3ms,
    wire 1ms, stage1 compute 20ms (the stall), retire 1ms — the dominant
    stall must name stage1's compute and the ranks/stages/mbs must cover
    the whole path."""
    spans = [
        {"cat": "serve", "name": "admit:interactive", "rank": 0,
         "t0": 0, "t1": _ms(2), "rid": "q1"},
        {"cat": "feed", "name": "mb0", "rank": 0, "mb": 0,
         "t0": _ms(2), "t1": _ms(3), "rid": "q1"},
        {"cat": "compute", "name": "stage0", "rank": 0, "stage": 0,
         "mb": 0, "t0": _ms(3), "t1": _ms(6), "rid": "q1"},
        {"cat": "wire", "name": "send->r1", "rank": 0, "mb": 0,
         "t0": _ms(6), "t1": _ms(7), "rid": "q1"},
        {"cat": "stage", "name": "dispatch", "rank": 1, "stage": 1,
         "mb": 0, "t0": _ms(7), "t1": _ms(27), "rid": "q1"},
        {"cat": "results", "name": "deliver", "rank": 0, "mb": 0,
         "t0": _ms(27), "t1": _ms(28), "rid": "q1"},
        # another request's spans must not contaminate the timeline
        {"cat": "compute", "name": "stage0", "rank": 0, "stage": 0,
         "mb": 5, "t0": 0, "t1": _ms(50), "rid": "q2"},
    ]
    tl = report.request_timeline(spans, "q1")
    assert tl["found"] and tl["spans"] == 6
    assert tl["ranks"] == [0, 1] and tl["stages"] == [0, 1]
    assert tl["mbs"] == [0] and tl["total_ms"] == 28.0
    assert tl["dominant_stall"]["segment"] == "stage1/dispatch"
    assert tl["dominant_stall"]["busy_ms"] == 20.0
    assert tl["segments"]["queue_wait"]["busy_ms"] == 2.0
    assert tl["segments"]["wire/send->r1"]["busy_ms"] == 1.0
    assert tl["unattributed_ms"] == 0.0
    assert report.request_timeline(spans, "nope") == {"rid": "nope",
                                                      "found": False}


def test_analyze_spans_requests_section():
    spans = [
        {"cat": "serve", "name": "generate", "rank": 0,
         "t0": 0, "t1": _ms(30), "rid": "q1"},
        {"cat": "serve", "name": "generate", "rank": 0,
         "t0": 0, "t1": _ms(5), "rid": "q2"},
        {"cat": "compute", "name": "stage0", "rank": 0, "stage": 0,
         "t0": 0, "t1": _ms(1)},
    ]
    rec = report.analyze_spans(spans, span_cost_ns=100.0)
    assert rec["requests"]["n"] == 2
    assert rec["requests"]["worst"][0] == {"rid": "q1", "ms": 30.0}
    # traces without rids carry an empty section, not a crash
    rec2 = report.analyze_spans(spans[-1:], span_cost_ns=100.0)
    assert rec2["requests"] == {}


def test_worst_exemplar_of_a_scrape_resolves_to_its_timeline():
    """The p99 -> trace link, end to end without a server: a latency
    histogram's exposition is parsed back into exemplar rows, the worst
    row names the slow request, and that id resolves against the span
    list to a timeline with a dominant stall."""
    reg = prom.Registry()
    h = reg.histogram("pipeedge_serve_request_latency_seconds", "t",
                      buckets=(0.01, 0.1))
    spans = []
    for rid, ms in (("q1", 4), ("q2", 60), ("q3", 7)):
        h.observe(ms / 1e3, exemplar=rid)
        spans += [
            {"cat": "serve", "name": "admit:interactive", "rank": 0,
             "t0": 0, "t1": _ms(1), "rid": rid},
            {"cat": "compute", "name": "stage1", "rank": 0, "stage": 1,
             "mb": 0, "t0": _ms(1), "t1": _ms(ms), "rid": rid}]
    rows = prom.parse_exemplars(reg.render(),
                                "pipeedge_serve_request_latency_seconds")
    assert {r["trace_id"] for r in rows} == {"q2", "q3"}   # a row a bucket
    worst = max(rows, key=lambda r: r["value"])
    assert worst["trace_id"] == "q2" and worst["le"] == "0.1"
    tl = report.request_timeline(spans, worst["trace_id"])
    assert tl["found"] and tl["total_ms"] == 60.0
    assert tl["dominant_stall"]["segment"] == "stage1/compute"
    assert tl["dominant_stall"]["busy_ms"] == 59.0


# -- loadgen worst-N -----------------------------------------------------

def test_loadgen_stats_worst_n_and_deadline_rids():
    from tools import loadgen
    st = loadgen._Stats(["interactive"])
    for i in range(8):
        st.record("interactive", "ok", latency_ms=float(i), rid=f"q{i}")
    st.record("interactive", "deadline", rid="q504")
    assert [w[1] for w in st.worst["interactive"]] \
        == ["q7", "q6", "q5", "q4", "q3"]
    assert st.deadline_rids == ["q504"]


def test_streaming_shed_counts_class_outcome_matrix():
    """A STREAMING request shed at admission never reaches generate(),
    so the request-class x outcome matrix (and the endpoint counter)
    must be settled on the streaming path itself — the 503s and the
    matrix have to reconcile under a shed storm of streaming clients."""
    import urllib.error
    import urllib.request
    from http.server import ThreadingHTTPServer

    from pipeedge_tpu.serving import AdmissionShed
    from tools import serve as serve_mod

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    total = registry.get_model_layers("pipeedge/test-tiny-gpt2")
    _, params, _ = registry.module_shard_factory(
        "pipeedge/test-tiny-gpt2", None, 1, total, unroll=False)
    pipe = decode.DecodePipeline(
        registry.get_model_entry("pipeedge/test-tiny-gpt2").family.FAMILY,
        registry.get_model_config("pipeedge/test-tiny-gpt2"),
        [(1, total)], [params], max_len=32)
    svc = serve_mod._Service(pipe)
    try:
        def always_shed(request_class, deadline_s=None, rid=None,
                        tokens=0):
            raise AdmissionShed(request_class, "queue_full", 1.25)

        svc.admit = always_shed
        shed_before = svc.m_class_outcome.value(
            **{"class": "interactive", "outcome": "shed"})
        server = ThreadingHTTPServer(
            ("127.0.0.1", 0), serve_mod.make_handler(svc, "tiny"))
        port = server.server_address[1]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps({"ids": [[1, 2, 3]], "new_tokens": 2,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(req, timeout=30)
            exc = exc_info.value
            body = json.loads(exc.read())
            # the shed surfaced as a real 503 (headers never committed),
            # rid included, and BOTH counters moved
            assert exc.code == 503 and body["shed"] and body["rid"]
            assert exc.headers.get("Retry-After") == "1.25"
            assert svc.m_class_outcome.value(
                **{"class": "interactive", "outcome": "shed"}) \
                == shed_before + 1
        finally:
            server.shutdown()
            t.join(timeout=10)
    finally:
        svc.stop()


# -- tier-1 loopback acceptance -----------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port, path, obj, timeout=120):
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


def _get(port, path, timeout=30):
    import urllib.request
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as resp:
        return json.loads(resp.read())


@pytest.mark.fleet
def test_traced_serve_stall_attribution(tmp_path):
    """The acceptance run (ISSUE 10): a traced serve with a deterministic
    80 ms stall injected into stage 1 answers one request; its rid (from
    the response body) must resolve via `trace_report --request` to a
    timeline whose dominant stall names stage 1. A second request with a
    too-small deadline must 504, auto-writing a postmortem bundle that
    /healthz names and that trace_report can read directly."""
    port = _free_port()
    trace_path = tmp_path / "serve_trace.json"
    pm_dir = tmp_path / "postmortems"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "-m", "pipeedge/test-tiny-gpt2", "-pt", "1,4,5,8",
         "--max-len", "48", "-t", "float32", "--port", str(port),
         "--trace-spans", str(trace_path),
         "--inject-stall", "1:80",
         "--postmortem-dir", str(pm_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if "serving" in line:
                break
            if proc.poll() is not None:
                raise RuntimeError(f"server died: {proc.stdout.read()}")
        else:
            raise RuntimeError("server never came up")
        # keep reading: a pipe nobody reads holds 64 KB, and a server that
        # has logged that much (XLA says two long lines for every program
        # it loads from the compile cache) blocks in its next write
        threading.Thread(target=lambda: [None for _ in proc.stdout],
                         daemon=True).start()

        # 0) warmup: the first request pays the XLA compiles, and its
        #    timeline would (correctly!) name the compile as its
        #    dominant stall — the assertion below is about the
        #    steady-state stall, so compile the same shapes first
        status, _ = _post(port, "/generate",
                          {"ids": [[1, 2, 3, 4]], "new_tokens": 2})
        assert status == 200

        # 1) the traced request: every response carries its rid
        status, resp = _post(port, "/generate",
                             {"ids": [[1, 2, 3, 4]], "new_tokens": 4})
        assert status == 200 and resp["rid"]
        rid = resp["rid"]

        # 2) deadline small enough that the stage-1 stall eats it ->
        #    504 + rid + an automatic deadline postmortem bundle
        status, resp504 = _post(port, "/generate",
                                {"ids": [[1, 2, 3, 4]], "new_tokens": 40,
                                 "deadline_ms": 250})
        assert status == 504 and resp504.get("deadline_exceeded")
        assert resp504["rid"]
        h = _get(port, "/healthz")
        assert h["flight"]["postmortems_written_total"] >= 1
        bundle_path = h["flight"]["last_postmortem"]
        assert bundle_path and os.path.exists(bundle_path)
        # the 504 also burns the class's SLO budget, and the burn engine's
        # own `slo_burn` bundle may be the newest by now (it was, under
        # six test workers): the deadline bundle is the one the 504 wrote
        [bundle_path] = sorted(pm_dir.glob("postmortem-*-deadline.json"))
        bundle = json.load(open(bundle_path))
        assert bundle["trigger"] == "deadline"
        assert bundle["rid"] == resp504["rid"]
        assert any(e["kind"] == "deadline" for e in bundle["events"])
        assert "serving" in bundle["context"]

        # 3) manual dump on demand
        status, dump = _post(port, "/debug/dump", {"rid": rid})
        assert status == 200 and os.path.exists(dump["path"])

        # 4) /metrics: exemplars link the latency histogram to a rid,
        #    and the postmortem counter is shared with /healthz
        import urllib.request
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as f:
            metrics = f.read().decode()
        assert "# EXEMPLAR pipeedge_serve_request_latency_seconds_bucket" \
            in metrics
        assert "pipeedge_postmortems_written_total" in metrics
        assert 'trace_id="' in metrics
        # the rows parse back, and every one names a request this
        # server answered (resolved against the trace below)
        exemplars = prom.parse_exemplars(
            metrics, "pipeedge_serve_request_latency_seconds")
        assert exemplars and all(e["trace_id"] for e in exemplars)
        worst_rid = max(exemplars, key=lambda e: e["value"])["trace_id"]
    finally:
        proc.send_signal(signal.SIGTERM)   # trace written on unwind
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise

    # 5) trace_report --request: the dominant stall names stage 1 (the
    #    injected 80 ms sleep rides inside stage 1's exec span)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(trace_path), "--request", rid],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    tl = json.loads(out.stdout)
    assert tl["found"] and tl["rid"] == rid
    assert tl["dominant_stall"]["segment"] == "stage1/compute", \
        tl["dominant_stall"]
    assert tl["segments"].get("queue_wait") is not None
    assert 1 in tl["stages"]
    # an unknown rid exits 3, not 0
    missing = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(trace_path), "--request", "no-such-rid"],
        capture_output=True, text=True, env=env, timeout=120)
    assert missing.returncode == 3
    # the full report's requests section names traced requests
    full = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(trace_path)],
        capture_output=True, text=True, env=env, timeout=120)
    assert full.returncode == 0
    rec = json.loads(full.stdout)
    assert rec["requests"]["n"] >= 2
    assert any(w["rid"] == resp504["rid"] or w["rid"] == rid
               for w in rec["requests"]["worst"])
    # the scrape's worst exemplar resolves to a timeline of its own (the
    # p99 -> trace link), in process: the trace is already on disk
    with open(trace_path, encoding="utf8") as fh:
        spans = chrome_trace.trace_to_spans(json.load(fh))
    linked = report.request_timeline(spans, worst_rid)
    assert linked["found"] and linked["dominant_stall"] is not None
