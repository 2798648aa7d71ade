"""The hand-over between the decode executor and the HTTP front end: a
tick's tokens leave the executor in one call, one writer thread writes every
stream's lines, and a request's end wakes its own waiter (docs/SERVING.md,
"The threads of a server")."""
import http.client
import json
import socket
import struct
import threading
import time
from http.server import ThreadingHTTPServer

import numpy as np
import pytest

from pipeedge_tpu.parallel import batcher as batcher_mod
from pipeedge_tpu.parallel.batcher import ContinuousBatcher
from pipeedge_tpu.serving import streams

MODEL = "pipeedge/test-tiny-gpt2"
MAX_LEN = 48


@pytest.fixture(scope="module")
def pipe():
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    total = registry.get_model_layers(MODEL)
    _, params, _ = registry.module_shard_factory(MODEL, None, 1, total,
                                                 unroll=False)
    return decode.DecodePipeline(
        registry.get_model_entry(MODEL).family.FAMILY,
        registry.get_model_config(MODEL), [(1, total)], [params],
        max_len=MAX_LEN)


@pytest.fixture(scope="module")
def served(pipe):
    """tools/serve.py's service and handler in this process, so that a test
    can read the threads and the counters beside the bytes."""
    from tools import serve as serve_mod
    service = serve_mod._Service(pipe, max_active=8, brownout_enabled=False)
    server = ThreadingHTTPServer(("127.0.0.1", 0),
                                 serve_mod.make_handler(service, "tiny"))
    server.daemon_threads = True
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        yield service, server.server_address[1]
    finally:
        server.shutdown()
        thread.join(timeout=10)
        service.stop()


def _prompt(seed, length=6, rows=1):
    return np.random.default_rng(seed).integers(
        0, 100, size=(rows, length)).tolist()


def _raw_stream(port, obj, timeout=120):
    """POST a streaming /generate and return the response's body exactly as
    the server framed it, headers cut off."""
    body = json.dumps(obj).encode()
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.sendall(b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Type: application/json\r\nConnection: close\r\n"
                  b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
                  + body)
        raw = b""
        while True:
            part = s.recv(65536)
            if not part:
                break
            raw += part
    head, _, framed = raw.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200"), head
    assert b"Transfer-Encoding: chunked" in head
    return framed


def _unframe(framed):
    """The chunks of a chunked body, the terminating one included."""
    chunks = []
    while framed:
        size, _, rest = framed.partition(b"\r\n")
        n = int(size, 16)
        assert rest[n:n + 2] == b"\r\n"
        chunks.append(rest[:n])
        framed = rest[n + 2:]
    return chunks


def _parent_chunk(obj):
    """`Handler._chunk` as every server before the one writer framed a line
    (tools/serve.py at PR 56), byte for byte."""
    data = json.dumps(obj).encode() + b"\n"
    return f"{len(data):x}\r\n".encode() + data + b"\r\n"


def _stream(port, obj, timeout=120):
    """The lines of a streamed answer, each stamped as it arrived."""
    connection = http.client.HTTPConnection("127.0.0.1", port,
                                            timeout=timeout)
    try:
        connection.request("POST", "/generate", body=json.dumps(obj),
                           headers={"Content-Type": "application/json"})
        response = connection.getresponse()
        assert response.status == 200
        lines = []
        while True:
            line = response.readline()
            if not line:
                return lines
            lines.append((time.monotonic(), json.loads(line)))
    finally:
        connection.close()


def _post(port, obj, timeout=120):
    connection = http.client.HTTPConnection("127.0.0.1", port,
                                            timeout=timeout)
    try:
        connection.request("POST", "/generate", body=json.dumps(obj),
                           headers={"Content-Type": "application/json"})
        return json.loads(connection.getresponse().read())
    finally:
        connection.close()


# -- the bytes of a stream ----------------------------------------------------

def test_a_streams_lines_come_in_step_order_then_the_final_line_then_the_end(
        served, pipe):
    _, port = served
    ids, n = _prompt(1), 7
    chunks = _unframe(_raw_stream(port, {"ids": ids, "new_tokens": n,
                                         "stream": True}))
    assert chunks[-1] == b"" and len(chunks) == n + 2
    lines = [json.loads(c) for c in chunks[:-1]]
    assert [line["step"] for line in lines[:-1]] == list(range(n))
    assert list(lines[-1]) == ["ids", "first_token_ms", "steps", "rid"]
    assert lines[-1]["steps"] == n and lines[-1]["first_token_ms"] > 0
    want = np.asarray(pipe.generate(np.asarray(ids), n))
    assert lines[-1]["ids"] == want.tolist()
    assert [line["tokens"] for line in lines[:-1]] \
        == want[:, len(ids[0]):].T.tolist()


def test_a_streams_bytes_are_what_a_handler_thread_used_to_write(served):
    """Every chunk is one line, framed as `Handler._chunk` framed it, its
    keys in the order they had; the last chunk ends the body."""
    _, port = served
    framed = _raw_stream(port, {"ids": _prompt(2, rows=2), "new_tokens": 5,
                                "stream": True})
    lines = [json.loads(c) for c in _unframe(framed)[:-1]]
    assert all(list(line) == ["step", "tokens"] for line in lines[:-1])
    assert framed == b"".join(_parent_chunk(line) for line in lines) \
        + b"0\r\n\r\n"


def test_an_error_after_the_headers_is_the_streams_last_line(served):
    """A request that fails once the headers are out ends in `{"error",
    "rid"}` and the terminating chunk, as it did."""
    service, port = served
    real = service._generate_policied

    def failing(*a, **kw):
        raise RuntimeError("the stage fell over")
    service._generate_policied = failing
    try:
        chunks = _unframe(_raw_stream(port, {"ids": _prompt(3),
                                             "new_tokens": 3,
                                             "stream": True}))
    finally:
        service._generate_policied = real
    assert chunks[-1] == b"" and len(chunks) == 2
    last = json.loads(chunks[0])
    assert list(last) == ["error", "rid"]
    assert last["error"] == "the stage fell over" and last["rid"]


# -- whose tokens ---------------------------------------------------------------

def test_two_requests_that_step_together_get_their_own_tokens(served, pipe):
    """Eight streams at once, more than one of them in every step's
    hand-over: each gets its own continuation, line by line."""
    service, port = served
    before = (streams.M_HANDOVERS.value(), streams.M_STREAM_ROWS.value())
    prompts = {i: _prompt(10 + i, length=4 + i) for i in range(8)}
    got = {}

    def client(i):
        got[i] = _stream(port, {"ids": prompts[i], "new_tokens": 12,
                                "stream": True})
    threads = [threading.Thread(target=client, args=(i,)) for i in prompts]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=120)
    for i, ids in prompts.items():
        want = np.asarray(pipe.generate(np.asarray(ids), 12))
        lines = [line for _, line in got[i]]
        streamed = [line["tokens"][0] for line in lines[:-1]]
        assert streamed == want[0, len(ids[0]):].tolist(), i
        assert lines[-1]["ids"] == want.tolist(), i
    handovers = streams.M_HANDOVERS.value() - before[0]
    rows = streams.M_STREAM_ROWS.value() - before[1]
    assert rows == 8 * 12
    # rows that step together leave in one hand-over: fewer hand-overs
    # than lines (a one-row hand-over a token would read 96)
    assert 12 <= handovers < rows


@pytest.mark.parametrize("extra", [{}, {"temperature": 0.8, "seed": 7},
                                   {"eos_token": None}],
                         ids=["greedy", "sampled-steps-alone", "eos"])
def test_streamed_answers_are_token_identical_to_a_solo_generate(
        served, pipe, extra):
    """A request that steps with the others and one that steps alone (a
    sampled one: its picks split its own key) go through the same writer."""
    _, port = served
    ids, n = _prompt(21, rows=2), 9
    if "eos_token" in extra:
        # a token row 0 picks on its way: the final line is masked behind
        # it, as the non-streamed answer is (`generate()` has no eos)
        greedy = np.asarray(pipe.generate(np.asarray(ids), n))
        extra = {"eos_token": int(greedy[0, len(ids[0]) + 2])}
        want = _post(port, dict({"ids": ids, "new_tokens": n}, **extra))["ids"]
        assert want != greedy.tolist()
    else:
        want = np.asarray(pipe.generate(np.asarray(ids), n, **extra)).tolist()
    lines = [line for _, line in _stream(
        port, dict({"ids": ids, "new_tokens": n, "stream": True}, **extra))]
    assert lines[-1]["ids"] == want
    assert [line["step"] for line in lines[:-1]] == list(range(len(lines) - 1))
    assert lines[-1]["steps"] == len(lines) - 1


def test_a_non_streamed_generate_answers_as_before_beside_running_streams(
        served, pipe):
    service, port = served
    ids = _prompt(31, rows=2)
    want = np.asarray(pipe.generate(np.asarray(ids), 6))
    results = {}

    def streamer():
        results["stream"] = _stream(port, {"ids": _prompt(32),
                                           "new_tokens": 30, "stream": True})
    thread = threading.Thread(target=streamer)
    thread.start()
    out = _post(port, {"ids": ids, "new_tokens": 6})
    thread.join(timeout=120)
    assert out["ids"] == want.tolist() and list(out) == ["ids", "rid"]
    assert results["stream"][-1][1]["steps"] == 30


def test_a_streamed_request_is_one_thread_and_one_writer_serves_all(served):
    """While a stream runs the process holds the executor's worker, the
    one writer and that request's handler: no thread a stream beside it."""
    service, port = served
    seen = []
    real = service.streams.hand_over

    def spy(rows):
        seen.append(sorted(t.name for t in threading.enumerate()))
        real(rows)
    service.executor.on_tokens = spy
    try:
        _stream(port, {"ids": _prompt(41), "new_tokens": 8, "stream": True})
    finally:
        service.executor.on_tokens = real
    assert seen
    for names in seen:
        assert names.count("stream-writer") == 1
        assert names.count("decode-executor") == 1
        handlers = [n for n in names if "process_request_thread" in n]
        assert len(handlers) == 1, names


# -- a client that leaves, a client that stalls ------------------------------------

def test_a_disconnect_mid_stream_cancels_at_the_next_pick_and_frees_the_slot(
        served):
    service, port = served
    tokens_before = service.executor.snapshot()["tokens"]
    body = json.dumps({"ids": _prompt(51, length=3), "new_tokens": 40,
                       "stream": True}).encode()
    with socket.create_connection(("127.0.0.1", port), timeout=60) as sock:
        sock.sendall(b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: " + str(len(body)).encode()
                     + b"\r\n\r\n" + body)
        seen = b""
        while seen.count(b'"step"') < 2:
            part = sock.recv(4096)
            assert part, seen
            seen += part
        # vanish with an RST, so the writer's next send fails at once
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
    deadline = time.monotonic() + 60
    while service.executor.snapshot()["active"] > 0:
        assert time.monotonic() < deadline, "the slot was never freed"
        time.sleep(0.02)
    assert service.executor.snapshot()["tokens"] - tokens_before < 40
    assert _post(port, {"ids": _prompt(52), "new_tokens": 2})["ids"]


class _Flag:
    """A cancel flag that remembers when it was set."""

    def __init__(self):
        self.at = None

    def set(self):
        if self.at is None:
            self.at = time.monotonic()

    def is_set(self):
        return self.at is not None


def _pair(sndbuf=None):
    """(the server's end, the client's end) of one connection."""
    ours, theirs = socket.socketpair()
    if sndbuf is not None:
        ours.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
        theirs.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, sndbuf)
    return ours, theirs


@pytest.mark.parametrize("bound", ["buffer", "stall"])
def test_a_client_that_stops_reading_delays_no_other_streams_tokens(
        monkeypatch, bound):
    """Two streams in every hand-over; one client never reads. The other's
    lines all arrive, each within a bounded time of its hand-over, and the
    stalled stream is dropped as a disconnected one is: past the bytes it
    may hold, or past the time its socket may take nothing."""
    if bound == "buffer":
        monkeypatch.setattr(streams, "BUFFER_BYTES", 1 << 14)
    else:
        monkeypatch.setattr(streams, "STALL_SECONDS", 0.3)
    writer = streams.StreamWriter().start()
    stalled_end, stalled_peer = _pair(sndbuf=4096)
    live_end, live_peer = _pair()
    stalled_flag, live_flag = _Flag(), _Flag()
    t0 = time.monotonic()
    stalled = streams.Stream(stalled_end, "stalled", stalled_flag, t0)
    live = streams.Stream(live_end, "live", live_flag, t0)
    steps, width = 400, 64          # lines of about 400 bytes
    sent_at, got_at = [], []

    def reader():
        file = live_peer.makefile("rb")
        while len(got_at) < steps:
            size = file.readline()
            line = file.read(int(size, 16) + 2)
            assert json.loads(line)["step"] == len(got_at)
            got_at.append(time.monotonic())
    thread = threading.Thread(target=reader, daemon=True)
    thread.start()
    try:
        for step in range(steps):
            token = np.full((width,), step, np.int32)
            sent_at.append(time.monotonic())
            writer.hand_over([(stalled, step, token), (live, step, token)])
            time.sleep(0.002)
        thread.join(timeout=30)
        assert len(got_at) == steps
        assert max(g - s for g, s in zip(got_at, sent_at)) < 1.0
        deadline = time.monotonic() + 10
        while not stalled_flag.is_set():
            assert time.monotonic() < deadline, "never dropped"
            time.sleep(0.01)
        assert not live_flag.is_set()
        # both requests end: the live stream gets its final line, the
        # dropped one closes without another byte
        writer.finish(live, ids=[[1, 2]])
        writer.finish(stalled, ids=[[1, 2]])
        assert live.closed.wait(10) and stalled.closed.wait(10)
        assert not stalled.unsent
        live_peer.settimeout(10)
        tail = b""
        while not tail.endswith(b"0\r\n\r\n"):
            tail += live_peer.recv(4096)
        assert json.loads(_unframe(tail)[0])["steps"] == steps
    finally:
        writer.stop()
        for sock in (stalled_end, stalled_peer, live_end, live_peer):
            sock.close()


def test_a_slow_reader_gets_every_byte_in_order():
    """What the kernel does not take at once waits in the stream's own
    buffer and goes out, in order, as the client reads."""
    writer = streams.StreamWriter().start()
    ours, theirs = _pair(sndbuf=4096)
    flag = _Flag()
    stream = streams.Stream(ours, "slow", flag, time.monotonic())
    steps = 120
    try:
        for step in range(steps):
            writer.hand_over([(stream, step, np.full((64,), step, np.int32))])
        writer.finish(stream, ids=[[7]])
        time.sleep(0.2)             # the writer holds most of it by now
        assert not stream.closed.is_set()
        theirs.settimeout(10)
        raw = b""
        while not raw.endswith(b"0\r\n\r\n"):
            raw += theirs.recv(512)
            time.sleep(0.001)
        lines = [json.loads(c) for c in _unframe(raw)[:-1]]
        assert [line["step"] for line in lines[:-1]] == list(range(steps))
        assert lines[-1]["ids"] == [[7]] and lines[-1]["steps"] == steps
        assert stream.closed.wait(10) and not flag.is_set()
    finally:
        writer.stop()
        ours.close()
        theirs.close()


# -- the executor's side -------------------------------------------------------------

def test_a_ticks_tokens_leave_the_executor_in_one_hand_over(pipe):
    """Four requests submitted before the first tick step together: every
    step's tokens arrive in one call of `on_tokens`, each row under its own
    stream's handle, and a library caller's `on_token` fires beside it."""
    import jax.numpy as jnp
    calls, own = [], []
    executor = ContinuousBatcher(pipe, max_active=4, on_tokens=calls.append)
    n = 6
    for i in range(4):
        executor.submit(f"r{i}", jnp.asarray(_prompt(60 + i)), n,
                        stream=f"s{i}",
                        on_token=(lambda step, tok: own.append(
                            (step, np.asarray(tok).tolist())))
                        if i == 0 else None)
    results = executor.run()
    assert sum(len(rows) for rows in calls) == 4 * n
    assert max(len(rows) for rows in calls) == 4
    assert len(calls) < 4 * n
    for i in range(4):
        mine = [(step, np.asarray(tok).tolist()) for rows in calls
                for handle, step, tok in rows if handle == f"s{i}"]
        assert [step for step, _ in mine] == list(range(n))
        assert [tok[0] for _, tok in mine] \
            == results[f"r{i}"][0, -n:].tolist()
        if i == 0:
            assert own == mine


def test_a_completion_wakes_only_its_own_waiter(pipe):
    """32 overlapping requests, a thread each in `wait()`: nobody is woken
    by another request's end."""
    import jax.numpy as jnp
    before = {kind: batcher_mod.M_WAKEUPS.value(kind=kind)
              for kind in ("own", "other")}
    executor = ContinuousBatcher(pipe, max_active=8).start()
    outs, errors = {}, []

    def client(i):
        try:
            executor.submit(i, jnp.asarray(_prompt(70 + i, length=3 + i % 5)),
                            2 + i % 9)
            outs[i] = executor.wait(i, timeout=120)
        except BaseException as exc:    # noqa: BLE001 - reported below
            errors.append(exc)
    threads = [threading.Thread(target=client, args=(i,)) for i in range(32)]
    try:
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=120)
    finally:
        executor.stop()
    assert not errors and len(outs) == 32
    assert all(outs[i].shape[1] == 3 + i % 5 + 2 + i % 9 for i in outs)
    assert batcher_mod.M_WAKEUPS.value(kind="other") == before["other"]
    assert 1 <= batcher_mod.M_WAKEUPS.value(kind="own") - before["own"] <= 32
    assert not executor._waiters


@pytest.mark.parametrize("end", ["die", "stop"])
def test_the_executors_end_still_wakes_every_waiter(pipe, end):
    """A worker that dies and a `stop()` fail every waiter at once, and
    every later `submit` and `wait`."""
    import jax.numpy as jnp
    executor = ContinuousBatcher(pipe, max_active=2)    # never started:
    errors, waiting = {}, threading.Barrier(7)          # nothing finishes

    def client(i):
        executor.submit(i, jnp.asarray(_prompt(80 + i)), 4)
        waiting.wait(timeout=30)
        try:
            executor.wait(i, timeout=60)
        except RuntimeError as exc:
            errors[i] = str(exc)
    threads = [threading.Thread(target=client, args=(i,)) for i in range(6)]
    for thread in threads:
        thread.start()
    waiting.wait(timeout=30)
    deadline = time.monotonic() + 30
    while len(executor._waiters) < 6:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    started = time.monotonic()
    if end == "die":
        executor._die(ValueError("the device went away"))
    else:
        executor.stop()
    for thread in threads:
        thread.join(timeout=30)
    assert time.monotonic() - started < 5.0
    assert sorted(errors) == list(range(6))
    assert all("serving worker died" in message for message in errors.values())
    with pytest.raises(RuntimeError):
        executor.wait("late", timeout=1)
    with pytest.raises(RuntimeError):
        executor.submit("later", jnp.asarray(_prompt(90)), 2)


def test_a_wait_that_times_out_leaves_no_waiter_behind(pipe):
    import jax.numpy as jnp
    executor = ContinuousBatcher(pipe, max_active=2)
    executor.submit("r", jnp.asarray(_prompt(95)), 3)
    with pytest.raises(TimeoutError):
        executor.wait("r", timeout=0.05)
    assert not executor._waiters
    assert executor.run()["r"].shape == (1, 9)
    assert executor.wait("r", timeout=1).shape == (1, 9)


def test_metrics_render_the_hand_over_and_the_wake_up_counters(served):
    service, port = served
    _stream(port, {"ids": _prompt(99), "new_tokens": 4, "stream": True})
    connection = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        connection.request("GET", "/metrics")
        text = connection.getresponse().read().decode()
    finally:
        connection.close()
    samples = dict(line.rsplit(" ", 1) for line in text.splitlines()
                   if line and not line.startswith("#"))
    assert float(samples["pipeedge_stream_handovers_total"]) >= 4
    assert float(samples["pipeedge_stream_rows_total"]) \
        >= float(samples["pipeedge_stream_handovers_total"])
    assert float(samples['pipeedge_wait_wakeups_total{kind="other"}']) == 0
    assert float(samples['pipeedge_wait_wakeups_total{kind="own"}']) >= 1
