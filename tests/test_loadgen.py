"""`tools/loadgen.py`: seeded arrival schedules and spec parsers, the
report's totals and its accounting of requests the client gave up on
(against a stub server, in-process)."""
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from tools import loadgen
from tools.loadgen import arrival_offsets


def test_arrival_offsets_seeded_and_shaped():
    import random
    uniform = arrival_offsets(10, 5.0, "uniform")
    assert uniform == [i / 5.0 for i in range(10)]
    a = arrival_offsets(50, 5.0, "poisson", random.Random(7))
    b = arrival_offsets(50, 5.0, "poisson", random.Random(7))
    c = arrival_offsets(50, 5.0, "poisson", random.Random(8))
    assert a == b                      # same seed -> same schedule
    assert a != c and a != uniform[:50]
    gaps = [t1 - t0 for t0, t1 in zip(a, a[1:])]
    assert 0.05 < sum(gaps) / len(gaps) < 0.8   # mean gap ~ 1/qps
    with pytest.raises(ValueError, match="unknown arrival"):
        arrival_offsets(1, 1.0, "bursty")


def test_parse_ramp_spec():
    from tools.loadgen import parse_ramp_spec
    assert parse_ramp_spec(None) is None
    assert parse_ramp_spec("uniform") is None
    assert parse_ramp_spec("poisson") is None
    assert parse_ramp_spec("ramp:2:8") == {"lo": 2.0, "hi": 8.0,
                                           "hold": 1.0 / 3.0}
    assert parse_ramp_spec("ramp:1:4:0.5") == {"lo": 1.0, "hi": 4.0,
                                               "hold": 0.5}
    for bad in ("ramp:", "ramp:2", "ramp:2:8:0.3:9", "ramp:x:y",
                "ramp:0:8", "ramp:8:2", "ramp:2:8:1.0", "ramp:2:8:-0.1"):
        with pytest.raises(ValueError, match="ramp"):
            parse_ramp_spec(bad)


def test_ramp_offsets_shape_and_determinism():
    from tools.loadgen import ramp_rate
    ramp = {"lo": 2.0, "hi": 10.0, "hold": 1.0 / 3.0}
    a = arrival_offsets(0, None, "ramp:2:10", duration_s=12.0)
    b = arrival_offsets(0, None, "ramp:2:10", duration_s=12.0)
    assert a == b                      # deterministic grid, no RNG
    assert a[0] == 0.0 and a[-1] < 12.0
    # arrival count ~ integral of the rate: (lo+hi)/2 on each edge,
    # hi on the plateau -> 4*(2+10)/2 + 4*10 = 88 arrivals over 12 s
    assert 80 <= len(a) <= 96
    # instantaneous spacing tracks the piecewise-linear rate: gaps on
    # the plateau (~1/hi) are much tighter than at the ramp floor
    first_gap = a[1] - a[0]
    mid = min(range(len(a)), key=lambda i: abs(a[i] - 6.0))
    assert a[mid + 1] - a[mid] < first_gap / 2
    # rate endpoints and plateau value
    assert ramp_rate(0.0, 12.0, ramp) == 2.0
    assert ramp_rate(6.0, 12.0, ramp) == 10.0
    assert ramp_rate(12.0, 12.0, ramp) == 2.0
    with pytest.raises(ValueError, match="duration"):
        arrival_offsets(0, None, "ramp:2:10")


def test_parse_burst_spec():
    from tools.loadgen import parse_burst_spec
    assert parse_burst_spec(None) is None
    assert parse_burst_spec("0.5:3:48") == {
        "at": 0.5, "n": 3, "len": 48, "window_s": 2.0}
    assert parse_burst_spec("0.25:2:32:4.5") == {
        "at": 0.25, "n": 2, "len": 32, "window_s": 4.5}
    # dicts pass through (run_load callers hand the parsed form in)
    spec = {"at": 0.5, "n": 1, "len": 8, "window_s": 2.0}
    assert parse_burst_spec(spec) is spec
    for bad in ("1.5:3:48", "0.5:0:48", "0.5:3:0", "0.5:3", "x:y:z",
                "0.5:3:48:0"):
        with pytest.raises(ValueError):
            parse_burst_spec(bad)


# -- the report against a stub server -------------------------------------

# wide enough that a loaded test machine does not turn an `ok` late
SLO_MS = {"interactive": 800.0, "batch": 800.0, "best_effort": 800.0}

# what the stub answers for each outcome of the taxonomy: status, body,
# Retry-After, seconds it holds the request
ANSWERS = {
    "ok": (200, {"ids": [[1]]}, None, 0.0),
    "ok_late": (200, {"ids": [[1]]}, None, 1.0),        # over the 800 ms SLO
    "shed": (503, {"shed": True}, "1.5", 0.0),
    "degraded": (503, {"degraded": True}, "2", 0.0),
    "deadline": (504, {"deadline_exceeded": True}, None, 0.0),
    "error": (500, {"error": "boom"}, None, 0.0),
}


class _Stub:
    """An in-process /generate that answers by a function of the request
    body and counts what it was sent."""

    def __init__(self, outcome_of):
        stub = self
        self.bodies = []
        self._lock = threading.Lock()

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):                          # noqa: N802
                body = json.loads(self.rfile.read(
                    int(self.headers["Content-Length"])))
                with stub._lock:
                    n = len(stub.bodies)
                    stub.bodies.append(body)
                status, resp, retry_after, hold = ANSWERS[outcome_of(body)]
                time.sleep(hold)
                data = json.dumps(dict(resp, rid=f"q{n}")).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                if retry_after is not None:
                    self.send_header("Retry-After", retry_after)
                self.end_headers()
                self.wfile.write(data)

            def log_message(self, *args):
                pass

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.server.daemon_threads = True
        self.url = f"http://127.0.0.1:{self.server.server_port}/generate"
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True)
        self._thread.start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self._thread.join(timeout=10)


@pytest.fixture
def stub():
    made = []

    def make(outcome_of):
        made.append(_Stub(outcome_of))
        return made[-1]

    yield make
    for s in made:
        s.close()


@pytest.mark.parametrize("outcome", loadgen.OUTCOMES)
def test_every_request_lands_in_its_outcome_and_nowhere_else(stub, outcome):
    """One outcome of the taxonomy at a time: the totals hold every
    request under that name and zero under the others, the class rows add
    up to the totals, and each outcome leaves the side record it should
    (Retry-After values, the 504s' ids, the first error's text)."""
    server = stub(lambda body: outcome)
    rep = loadgen.run_load(server.url, 0.5, 16.0, slo_ms=SLO_MS, seed=4,
                           timeout=30)
    assert rep["requests"] == 8 == len(server.bodies)
    assert rep["client_dropped"] == 0
    assert rep["totals"] == {**dict.fromkeys(loadgen.OUTCOMES, 0),
                             outcome: 8}
    for name in loadgen.OUTCOMES:
        assert sum(c[name] for c in rep["classes"].values()) \
            == rep["totals"][name]
    assert sum(c["sent"] for c in rep["classes"].values()) == 8
    served = outcome in ("ok", "ok_late")
    assert rep["latency_ms"]["n"] == (8 if served else 0)
    for c in rep["classes"].values():
        if not c["sent"]:
            continue
        assert c["slo_attainment"] == (
            None if not served else 1.0 if outcome == "ok" else 0.0)
        assert (c["goodput_rps"] > 0) == (outcome == "ok")
        assert len(c["worst"]) == (min(c["sent"], loadgen.WORST_N)
                                   if served else 0)
    ra = rep["retry_after"]
    if outcome in ("shed", "degraded"):
        want = float(ANSWERS[outcome][2])
        assert (ra["n"], ra["min"], ra["max"], ra["distinct"]) \
            == (8, want, want, 1)
    else:
        assert ra == {"n": 0, "min": None, "max": None, "distinct": 0}
    assert len(rep["deadline_rids"]) == (
        loadgen.WORST_N if outcome == "deadline" else 0)
    if outcome == "error":
        assert "HTTP 500" in rep["first_error"] \
            and "boom" in rep["first_error"]
    else:
        assert rep["first_error"] is None
    # every request carried its class and, by default, its SLO as budget
    assert all(b["deadline_ms"] == SLO_MS[b["class"]]
               for b in server.bodies)


def test_mixed_outcomes_by_class_and_seeded_schedule(stub):
    """The server answers by class: each class row holds its own outcome,
    the totals are the rows' sums, attainment and goodput count only what
    was served in time, and the same seed sends the same classes and
    prompts again."""
    by_class = {"interactive": "ok", "batch": "shed",
                "best_effort": "deadline"}
    mix = {"interactive": 0.5, "batch": 0.3, "best_effort": 0.2}
    reports, sent = [], []
    for _ in range(2):
        server = stub(lambda body: by_class[body["class"]])
        reports.append(loadgen.run_load(
            server.url, 0.6, 40.0, mix=mix, slo_ms=SLO_MS, seed=9,
            prompt_len="uniform:3:7", deadline_from_slo=False, timeout=30))
        sent.append(sorted((b["class"], tuple(b["ids"][0]))
                           for b in server.bodies))
    rep = reports[0]
    assert rep["requests"] == 24 and rep["client_dropped"] == 0
    assert sent[0] == sent[1]
    for cls, outcome in by_class.items():
        row = rep["classes"][cls]
        assert row["sent"] > 0 and row[outcome] == row["sent"]
        assert rep["totals"][outcome] == row["sent"]
    assert rep["totals"]["error"] == 0 and rep["first_error"] is None
    assert rep["classes"]["interactive"]["slo_attainment"] == 1.0
    assert rep["classes"]["batch"]["slo_attainment"] is None
    assert rep["classes"]["batch"]["goodput_rps"] == 0.0
    assert all(3 <= len(ids) <= 7 for _, ids in sent[0])
    assert rep["prompt_len"] == {"dist": "uniform", "lo": 3, "hi": 7}
    assert all("deadline_ms" not in b for b in server.bodies)


def test_requests_over_the_inflight_cap_are_counted_as_dropped(stub):
    """The safety valve: with the server holding every answer and two
    requests allowed in flight, the launches the client could not make
    are `client_dropped`, never sent, in no outcome's count, and still
    part of `requests`: a wedged server cannot look like a polite one."""
    server = stub(lambda body: "ok_late")               # holds a second
    rep = loadgen.run_load(server.url, 0.5, 40.0, slo_ms=SLO_MS, seed=2,
                           max_inflight=2, timeout=30)
    sent = sum(c["sent"] for c in rep["classes"].values())
    assert rep["requests"] == 20
    assert sent == len(server.bodies) == sum(rep["totals"].values())
    assert rep["client_dropped"] == 20 - sent
    assert sent == 2 and rep["client_dropped"] == 18
    assert rep["totals"]["error"] == 0


def test_a_server_that_is_not_there_is_an_error_not_a_shed():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]                       # closed again here
    rep = loadgen.run_load(f"http://127.0.0.1:{port}/generate", 0.2, 20.0,
                           mix={"interactive": 1.0}, seed=1, timeout=5)
    assert rep["totals"]["error"] == rep["requests"] == 4
    assert rep["client_dropped"] == 0
    assert "interactive:" in rep["first_error"]
    assert rep["classes"]["interactive"]["goodput_rps"] == 0.0


def test_burst_spike_is_accounted_apart_from_the_steady_load(stub):
    """`--burst`: the spike's long prompts reach the server, report under
    `burst` with their own outcomes, and stay OUT of the class rows and
    totals, so goodput with and without a spike measures one offered
    load; served requests launched inside the window report as
    `during_ms`."""
    server = stub(lambda body: "ok")
    rep = loadgen.run_load(
        server.url, 0.6, 20.0, mix={"interactive": 1.0}, slo_ms=SLO_MS,
        seed=6, prompt_len=5, timeout=30,
        burst={"at": 0.5, "n": 3, "len": 31, "window_s": 0.2})
    assert rep["requests"] == 12
    assert rep["totals"]["ok"] == 12 == rep["classes"]["interactive"]["sent"]
    assert len(server.bodies) == 12 + 3
    assert sorted(len(b["ids"][0]) for b in server.bodies) \
        == [5] * 12 + [31] * 3
    burst = rep["burst"]
    assert (burst["n"], burst["ok"], burst["error"]) == (3, 3, 0)
    assert burst["prompt_len"] == 31 and burst["at_s"] == 0.3
    assert burst["first_error"] is None
    # arrivals every 50 ms: those launched in [0.3, 0.5] s are the window's
    assert 3 <= burst["during_ms"]["n"] <= 5
    assert burst["during_ms"]["p99"] is not None


def test_calibrate_counts_whole_requests_and_refuses_a_failing_server(stub):
    server = stub(lambda body: "ok")
    rate = loadgen.calibrate(server.url, 0.3, new_tokens=2,
                             prompt_len="uniform:2:9", timeout=30)
    assert rate > 0
    # the warm-up and every timed request: at the spec's LONGEST length
    assert len(server.bodies) >= 2
    assert {len(b["ids"][0]) for b in server.bodies} == {9}
    with pytest.raises(RuntimeError, match="HTTP 503"):
        loadgen.calibrate(stub(lambda body: "shed").url, 0.1, 2, 4,
                          timeout=30)
