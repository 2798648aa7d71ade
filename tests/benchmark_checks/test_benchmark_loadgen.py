"""The open-loop generator: a seed repeats its schedule, every seed has the
same amount of work, latency counts from the due time, and the generator's
own lateness is reported."""
import http.server
import json
import math
import threading
import time

import pytest

from benchmark import loadgen

TRAFFIC = {"rate_per_s": 20.0, "arrivals": "poisson",
           "prompt_len": {"choices": [4, 8, 16], "weights": [2, 1, 1]},
           "new_tokens": {"log_uniform": [2, 12]}}


def _shape(requests):
    return [(round(r.due_s, 9), tuple(r.ids), r.new_tokens) for r in requests]


def test_the_same_seed_gives_the_same_schedule():
    one = loadgen.schedule(TRAFFIC, 100, 5.0, 2 ** 31 + 7)
    two = loadgen.schedule(TRAFFIC, 100, 5.0, 2 ** 31 + 7)
    assert _shape(one) == _shape(two)
    assert _shape(one) != _shape(loadgen.schedule(TRAFFIC, 100, 5.0, 8))


def test_every_seed_has_the_same_work_in_another_order():
    one = loadgen.schedule(TRAFFIC, 100, 5.0, 1)
    two = loadgen.schedule(TRAFFIC, 100, 5.0, 2)
    assert len(one) == len(two) == 100
    for pick in (lambda r: len(r.ids), lambda r: r.new_tokens):
        assert sorted(map(pick, one)) == sorted(map(pick, two))
    gaps = lambda rs: sorted(round(b.due_s - a.due_s, 9)
                             for a, b in zip(rs, rs[1:]))
    # all gaps but the one each seed happens to put first
    assert len(set(gaps(one)) ^ set(gaps(two))) <= 2
    assert [len(r.ids) for r in one] != [len(r.ids) for r in two]


def test_lengths_follow_the_traffic_files_distribution():
    requests = loadgen.schedule(TRAFFIC, 100, 5.0, 3)
    lengths = [len(r.ids) for r in requests]
    assert (lengths.count(4), lengths.count(8), lengths.count(16)) \
        == (50, 25, 25)
    answers = sorted(r.new_tokens for r in requests)
    assert answers[0] == 2 and answers[-1] == 12
    mean = sum(answers) / len(answers)
    assert mean == pytest.approx(10 / math.log(6), rel=0.05)   # log-uniform


@pytest.mark.parametrize("arrivals,first_gap", [("uniform", 0.05),
                                                 ("poisson", None)])
def test_arrivals_keep_the_mean_rate(arrivals, first_gap):
    traffic = dict(TRAFFIC, arrivals=arrivals)
    requests = loadgen.schedule(traffic, 100, 5.0, 4)
    assert requests[0].due_s == 0.0
    assert requests[-1].due_s == pytest.approx(5.0, rel=0.05)
    if first_gap:
        assert requests[1].due_s == pytest.approx(first_gap)


def test_a_burst_shares_its_due_time_and_a_group_its_prefix():
    traffic = dict(TRAFFIC, burst=4,
                   shared_prefix={"groups": 2, "tokens": 3})
    requests = loadgen.schedule(traffic, 100, 5.0, 5)
    assert len({r.due_s for r in requests[:4]}) == 1
    assert requests[0].due_s < requests[4].due_s
    assert requests[0].ids[:3] == requests[2].ids[:3]
    assert requests[0].ids[:3] != requests[1].ids[:3]


@pytest.mark.parametrize("values,q,expected", [
    ([], 95, None), ([5.0], 95, 5.0),
    (list(range(1, 101)), 95, 95), (list(range(1, 101)), 50, 51),
    ([3, 1, 2], 0, 1), ([3, 1, 2], 100, 3),
])
def test_the_percentile_is_nearest_rank(values, q, expected):
    assert loadgen.percentile(values, q) == expected


class _Slow(http.server.BaseHTTPRequestHandler):
    """Streams one token line every 20 ms after 50 ms; one request at a
    time, so that a second request waits for the first."""
    lock = threading.Lock()
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def _chunk(self, row):
        data = (json.dumps(row) + "\n").encode()
        self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
        self.wfile.flush()

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        if body["new_tokens"] == 99:
            self.send_response(503)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"{}")
            return
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        with self.lock:
            time.sleep(0.05)
            for step in range(body["new_tokens"]):
                self._chunk({"step": step, "tokens": [7]})
                time.sleep(0.02)
        self._chunk({"ids": [body["ids"][0] + [7] * body["new_tokens"]],
                     "steps": body["new_tokens"]})
        self.wfile.write(b"0\r\n\r\n")


@pytest.fixture
def slow_server():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address[1]
    server.shutdown()
    server.server_close()


def test_latency_counts_from_the_due_time_and_lateness_is_reported(
        slow_server):
    requests = [loadgen.Request(index=i, due_s=0.0, ids=[1, 2], new_tokens=5)
                for i in range(2)]
    took = loadgen.drive("127.0.0.1", slow_server, requests)
    summary = loadgen.summarize(requests)
    assert summary["succeeded"] == 2 and summary["failed"] == 0
    assert summary["streamed_tokens"] == 10 and summary["tokens"] == 10
    first, second = sorted(summary["ttft_ms"])
    # the second was due at the same instant and waited for the first's
    # 50 + 5 x 20 ms: its first token counts from when it was due
    assert 45 <= first <= 120
    assert second >= first + 100
    assert len(summary["itl_ms"]) == 8
    assert 15 <= summary["itl_p50_ms"] <= 60
    assert 0 <= summary["gen_late_p95_ms"] < 50
    assert took >= 0.3
    assert all(r.answer == [1, 2] + [7] * 5 for r in requests)


def test_a_refused_request_misses_every_limit(slow_server):
    requests = [loadgen.Request(index=0, due_s=0.0, ids=[1], new_tokens=99),
                loadgen.Request(index=1, due_s=0.0, ids=[1], new_tokens=2)]
    loadgen.drive("127.0.0.1", slow_server, requests)
    summary = loadgen.summarize(requests)
    assert summary["failed"] == 1 and requests[0].status == 503
    assert summary["ttft_p95_ms"] == math.inf


@pytest.mark.parametrize("seed", [1, 23, 2 ** 31 + 7])
def test_no_seed_bunches_the_work(seed):
    """Every fifth of the window is offered about a fifth of the tokens and
    of the requests, whatever the seed: the order is drawn block by block."""
    traffic = dict(TRAFFIC, rate_per_s=5.5,
                   new_tokens={"log_uniform": [8, 96]})
    requests = loadgen.schedule(traffic, 100, 50.0, seed)
    tokens = [0] * 5
    count = [0] * 5
    for request in requests:
        fifth = min(4, int(request.due_s // 10))
        tokens[fifth] += request.new_tokens
        count[fifth] += 1
    mean = sum(tokens) / 5
    assert all(abs(t - mean) < 0.15 * mean for t in tokens), tokens
    assert all(abs(c - 55) <= 8 for c in count), count
