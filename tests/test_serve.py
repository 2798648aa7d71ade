"""HTTP serving front end (tools/serve.py): tokens over the wire match
solo DecodePipeline runs; prefix registration is reused across requests."""
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "pipeedge/test-tiny-gpt2"

pytestmark = pytest.mark.fleet      # spawns the server process


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port, path, obj, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _spawn_server(extra_args=()):
    """Start tools/serve.py on a free port; yield the port, then stop it
    (one copy of the spawn/readiness/teardown logic for every fixture)."""
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "-m", MODEL, "-pt", "1,4,5,8", "--max-len", "48",
         "-t", "float32", "--port", str(port), *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
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
        yield port
    finally:
        proc.terminate()
        proc.wait(timeout=10)


@pytest.fixture(scope="module")
def server():
    yield from _spawn_server()


@pytest.fixture(scope="module")
def solo_pipe():
    import jax

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    del jax
    total = registry.get_model_layers(MODEL)
    partition = [(1, 4), (5, 8)]
    params = []
    for i, (l, r) in enumerate(partition):
        _, p, _ = registry.module_shard_factory(MODEL, None, l, r, stage=i,
                                                unroll=False)
        params.append(p)
    return decode.DecodePipeline(
        registry.get_model_entry(MODEL).family.FAMILY,
        registry.get_model_config(MODEL), partition, params, max_len=48)


def test_healthz_and_generate_matches_solo(server, solo_pipe):
    port = server
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        health = json.loads(resp.read())
    assert health["ok"] and health["stages"] == 2
    assert health["speculative"] is False

    rng = np.random.default_rng(3)
    ids = rng.integers(0, 100, size=(2, 8)).tolist()
    got = _post(port, "/generate", {"ids": ids, "new_tokens": 6})["ids"]
    want = np.asarray(solo_pipe.generate(np.asarray(ids), 6))
    np.testing.assert_array_equal(np.asarray(got), want)

    # stats surface in /healthz after work has flowed
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        stats = json.loads(resp.read())["stats"]
    assert stats["tokens"] >= 6 and stats["stage_steps"] > 0
    assert stats["active"] == 0 and stats["pending"] == 0

    # sampled request with a seed reproduces the solo rng discipline
    got_s = _post(port, "/generate", {"ids": ids, "new_tokens": 5,
                                      "temperature": 0.8, "seed": 7})["ids"]
    want_s = np.asarray(solo_pipe.generate(np.asarray(ids), 5,
                                           temperature=0.8, seed=7))
    np.testing.assert_array_equal(np.asarray(got_s), want_s)


def test_prefix_registration_reused(server, solo_pipe):
    port = server
    rng = np.random.default_rng(9)
    prefix = rng.integers(0, 100, size=(6,)).tolist()
    reg = _post(port, "/prefix", {"ids": prefix})
    assert reg["len"] == 6
    handle = solo_pipe.precompute_prefix(np.asarray([prefix]))

    for seed in (0, 1):
        suffix = rng.integers(0, 100, size=(1, 4)).tolist()
        got = _post(port, "/generate",
                    {"ids": suffix, "new_tokens": 6,
                     "prefix_id": reg["prefix_id"]})["ids"]
        want = np.asarray(solo_pipe.generate(np.asarray(suffix), 6,
                                             prefix=handle))
        np.testing.assert_array_equal(np.asarray(got), want)

    # unknown prefix id is a clean 400
    try:
        _post(port, "/generate", {"ids": [[1, 2]], "new_tokens": 2,
                                  "prefix_id": "nope"})
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as exc:
        assert exc.code == 400


def test_malformed_requests_clean_400(server):
    """Bad inputs never wedge the serving worker: empty prompts and
    unknown paths get clean JSON errors, and the service keeps serving."""
    port = server
    for bad in ({"ids": [], "new_tokens": 2},
                {"ids": [[]], "new_tokens": 2},
                {"ids": [[1, 2]], "new_tokens": 0}):
        try:
            _post(port, "/generate", bad)
            raise AssertionError(f"expected HTTP 400 for {bad}")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400
    # still alive and serving afterwards
    got = _post(port, "/generate", {"ids": [[5, 6, 7]], "new_tokens": 2})
    assert len(got["ids"][0]) == 5


@pytest.fixture(scope="module")
def spec_server():
    # the shared -pt matches solo_pipe: per-stage random init is seeded
    # per shard, so weights only match the oracle when partitions match
    yield from _spawn_server(("--draft-model", MODEL, "--gamma", "3"))


def test_speculative_serving_matches_plain(spec_server, solo_pipe):
    """--draft-model: requests with "speculative": true return tokens
    identical to plain greedy (here the draft IS the target, so every
    proposal is accepted); prefix registration feeds both models; the
    sampling composition is refused cleanly."""
    port = spec_server
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        assert json.loads(resp.read())["speculative"] is True
    rng = np.random.default_rng(13)
    ids = rng.integers(0, 100, size=(2, 8)).tolist()
    plain = _post(port, "/generate", {"ids": ids, "new_tokens": 6})["ids"]
    spec = _post(port, "/generate", {"ids": ids, "new_tokens": 6,
                                     "speculative": True})["ids"]
    np.testing.assert_array_equal(np.asarray(spec), np.asarray(plain))

    prefix = rng.integers(0, 100, size=(6,)).tolist()
    reg = _post(port, "/prefix", {"ids": prefix})
    suffix = rng.integers(0, 100, size=(1, 4)).tolist()
    got = _post(port, "/generate",
                {"ids": suffix, "new_tokens": 5, "speculative": True,
                 "prefix_id": reg["prefix_id"]})["ids"]
    handle = solo_pipe.precompute_prefix(np.asarray([prefix]))
    want = np.asarray(solo_pipe.generate(np.asarray(suffix), 5,
                                         prefix=handle))
    np.testing.assert_array_equal(np.asarray(got), want)

    try:
        _post(port, "/generate", {"ids": ids, "new_tokens": 2,
                                  "speculative": True, "temperature": 0.7})
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as exc:
        assert exc.code == 400


def test_speculative_unavailable_without_draft(server):
    """The plain server (no --draft-model) refuses speculative requests
    with a clean 400."""
    try:
        _post(server, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2,
                                    "speculative": True})
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as exc:
        assert exc.code == 400


def test_served_executor_matches_solo_and_reports_stats(server, solo_pipe):
    """The executor's own worker thread, behind HTTP, produces the same
    tokens as solo runs; /healthz reports the one worker's stats."""
    port = server
    rng = np.random.default_rng(17)
    ids = rng.integers(0, 100, size=(2, 8)).tolist()
    got = _post(port, "/generate", {"ids": ids, "new_tokens": 6})["ids"]
    want = np.asarray(solo_pipe.generate(np.asarray(ids), 6))
    np.testing.assert_array_equal(np.asarray(got), want)

    # prefix reuse flows through the served executor too
    prefix = rng.integers(0, 100, size=(6,)).tolist()
    reg = _post(port, "/prefix", {"ids": prefix})
    suffix = rng.integers(0, 100, size=(1, 4)).tolist()
    got_p = _post(port, "/generate", {"ids": suffix, "new_tokens": 5,
                                      "prefix_id": reg["prefix_id"]})["ids"]
    handle = solo_pipe.precompute_prefix(np.asarray([prefix]))
    want_p = np.asarray(solo_pipe.generate(np.asarray(suffix), 5,
                                           prefix=handle))
    np.testing.assert_array_equal(np.asarray(got_p), want_p)

    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        health = json.loads(resp.read())
    assert health["executor"] == "wave"
    stats = health["stats"]
    # two stages: every token of both requests took two stage-steps
    assert stats["stage_steps"] >= 2 * (6 + 5)
    assert stats["ticks"] > 0 and stats["tokens"] >= 2 * 6 + 5
    assert stats["active"] == 0 and stats["pending"] == 0


def _stream_lines(port, obj, timeout=120):
    """POST a streaming /generate and return (lines, t_first, t_total):
    parsed x-ndjson lines plus client-side first-line/total wall times."""
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        t0 = time.monotonic()
        conn.request("POST", "/generate", json.dumps(obj),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.headers["Content-Type"] == "application/x-ndjson"
        lines, t_first = [], None
        while True:
            line = resp.readline()
            if not line:
                break
            if t_first is None:
                t_first = time.monotonic() - t0
            lines.append(json.loads(line))
        return lines, t_first, time.monotonic() - t0
    finally:
        conn.close()


@pytest.mark.parametrize("fixture_name", ["server", "tight_server"])
def test_streaming_generate(fixture_name, request, solo_pipe):
    """"stream": true returns one x-ndjson line per decode step followed
    by a final line whose ids equal the non-streaming response; the
    final line records server-side first-token latency. Works with
    slots to spare and on the single-slot server."""
    port = request.getfixturevalue(fixture_name)
    rng = np.random.default_rng(21)
    ids = rng.integers(0, 100, size=(2, 8)).tolist()
    n = 6
    lines, t_first, t_total = _stream_lines(
        port, {"ids": ids, "new_tokens": n, "stream": True})

    steps, final = lines[:-1], lines[-1]
    assert [ln["step"] for ln in steps] == list(range(n))
    assert final["steps"] == n
    assert final["first_token_ms"] is not None
    assert 0 < final["first_token_ms"] <= t_total * 1e3
    want = np.asarray(solo_pipe.generate(np.asarray(ids), n))
    np.testing.assert_array_equal(np.asarray(final["ids"]), want)
    # the streamed per-step tokens ARE the result's continuation columns
    streamed = np.stack([np.asarray(ln["tokens"]) for ln in steps], axis=1)
    np.testing.assert_array_equal(streamed, want[:, len(ids[0]):])


def test_streaming_eos_final_line_is_masked(server, solo_pipe):
    """With eos_token, streamed step lines carry raw picked tokens while
    the final line applies the pad-after-eos masking — byte-identical
    to the non-streaming result."""
    port = server
    rng = np.random.default_rng(23)
    ids = rng.integers(0, 100, size=(2, 8)).tolist()
    plain = _post(port, "/generate",
                  {"ids": ids, "new_tokens": 6, "eos_token": 11})["ids"]
    lines, _, _ = _stream_lines(
        port, {"ids": ids, "new_tokens": 6, "eos_token": 11,
               "stream": True})
    np.testing.assert_array_equal(np.asarray(lines[-1]["ids"]),
                                  np.asarray(plain))
    assert len(lines) - 1 == lines[-1]["steps"]


@pytest.mark.parametrize("fixture_name", ["server", "tight_server"])
def test_concurrent_clients(fixture_name, request, solo_pipe):
    """Several clients hammering /generate concurrently (mixed plain,
    sampled, prefix, streaming) each get exactly their solo-run tokens —
    the executor isolation contract under real HTTP concurrency, whether
    they share the pipeline or queue for its single slot."""
    import threading
    port = request.getfixturevalue(fixture_name)
    rng = np.random.default_rng(29)
    prefix = rng.integers(0, 100, size=(6,)).tolist()
    reg = _post(port, "/prefix", {"ids": prefix})
    handle = solo_pipe.precompute_prefix(np.asarray([prefix]))

    jobs = []
    for i in range(3):
        ids = rng.integers(0, 100, size=(1, 5 + i)).tolist()
        want = np.asarray(solo_pipe.generate(np.asarray(ids), 5,
                                             temperature=0.7, seed=i))
        jobs.append(({"ids": ids, "new_tokens": 5, "temperature": 0.7,
                      "seed": i}, want))
    suffix = rng.integers(0, 100, size=(1, 4)).tolist()
    jobs.append(({"ids": suffix, "new_tokens": 5,
                  "prefix_id": reg["prefix_id"]},
                 np.asarray(solo_pipe.generate(np.asarray(suffix), 5,
                                               prefix=handle))))
    stream_ids = rng.integers(0, 100, size=(2, 7)).tolist()
    stream_want = np.asarray(solo_pipe.generate(np.asarray(stream_ids), 5))

    results = {}

    def plain_client(i, req):
        results[i] = np.asarray(_post(port, "/generate", req)["ids"])

    def stream_client():
        lines, _, _ = _stream_lines(
            port, {"ids": stream_ids, "new_tokens": 5, "stream": True})
        results["stream"] = np.asarray(lines[-1]["ids"])

    threads = [threading.Thread(target=plain_client, args=(i, req))
               for i, (req, _) in enumerate(jobs)]
    threads.append(threading.Thread(target=stream_client))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    for i, (_, want) in enumerate(jobs):
        np.testing.assert_array_equal(results[i], want)
    np.testing.assert_array_equal(results["stream"], stream_want)


def test_speculative_does_not_block_plain_requests(spec_server):
    """Round-4 advice: a long speculative generation must not serialize
    plain requests behind the service lock. Launch a long speculative
    request, then issue short plain requests while it runs; the plain
    requests complete well before the speculative one."""
    import threading
    port = spec_server
    rng = np.random.default_rng(31)
    long_ids = rng.integers(0, 100, size=(1, 8)).tolist()
    t_spec_done = [None]

    def spec_client():
        _post(port, "/generate", {"ids": long_ids, "new_tokens": 24,
                                  "speculative": True})
        t_spec_done[0] = time.monotonic()

    spec_thread = threading.Thread(target=spec_client)
    spec_thread.start()
    # issue plain requests while the speculative one is in flight; their
    # shapes were compiled by the earlier tests in this module, so they
    # are quick — without the dedicated spec lock they would all queue
    # behind the whole speculative generation
    done_before_spec = 0
    for i in range(3):
        ids = rng.integers(0, 100, size=(2, 8)).tolist()
        out = _post(port, "/generate", {"ids": ids, "new_tokens": 2})
        assert len(out["ids"][0]) == 10
        if t_spec_done[0] is None:
            done_before_spec += 1
    spec_thread.join(timeout=300)
    assert not spec_thread.is_alive()
    assert done_before_spec >= 1
    # healthz stayed responsive throughout and reports clean state
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        assert json.loads(resp.read())["ok"]


def test_streaming_bad_request_still_400(server):
    """Streaming requests validate BEFORE the chunked headers commit:
    unknown prefix ids and invalid arguments return plain HTTP 400
    exactly like the non-streaming path."""
    port = server
    for bad in ({"ids": [[1, 2]], "new_tokens": 0, "stream": True},
                {"ids": [[1, 2]], "new_tokens": 2, "stream": True,
                 "prefix_id": "nope"},
                {"ids": [[]], "new_tokens": 2, "stream": True}):
        try:
            _post(port, "/generate", bad)
            raise AssertionError(f"expected HTTP 400 for {bad}")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400


def _tiny_pipe(partition=None, max_len=64):
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    total = registry.get_model_layers(MODEL)
    partition = partition or [(1, total)]
    params = []
    for i, (l, r) in enumerate(partition):
        _, p, _ = registry.module_shard_factory(MODEL, None, l, r, stage=i,
                                                unroll=False)
        params.append(p)
    return decode.DecodePipeline(
        registry.get_model_entry(MODEL).family.FAMILY,
        registry.get_model_config(MODEL), partition, params, max_len=max_len)


def _await_live(ex, rid):
    """Until `rid` is in the executor's live set (its submit has landed)."""
    deadline = time.monotonic() + 120
    while rid not in (ex.live_rids() or ()):
        assert time.monotonic() < deadline, f"{rid!r} never submitted"
        time.sleep(0.01)


def test_executor_stop_wakes_pending_submitter():
    """stop() fails the waiter of a request that never got a slot, not
    only those in flight: "b" sits in `pending` behind "a" (max_active=1)
    and its wait raises instead of hanging forever.

    Deterministic by construction (this flaked under full-suite load
    when it was sleep-paced): both clients submit, in order, before the
    worker starts, "a" is known admitted once its FIRST token streams
    back (on_token fires from the worker), and it cannot complete early:
    its on_token holds every step 50 ms, so its 55 tokens outlast by far
    the thread switch between its first token and stop()."""
    import threading

    import jax.numpy as jnp

    from pipeedge_tpu.parallel.batcher import ContinuousBatcher

    ex = ContinuousBatcher(_tiny_pipe(), max_active=1)
    errs = {}
    first_token = threading.Event()

    def slow_token(step, tok):
        first_token.set()
        time.sleep(0.05)

    def client(rid, tokens, **kw):
        try:
            ex.submit(rid, jnp.zeros((1, 4), jnp.int32), tokens, **kw)
            ex.wait(rid, timeout=120)
        except RuntimeError as exc:
            errs[rid] = str(exc)

    # "a" will hold the only admission slot with a long generation
    t_a = threading.Thread(target=client, args=("a", 55), daemon=True,
                           kwargs={"on_token": slow_token})
    t_a.start()
    _await_live(ex, "a")
    t_b = threading.Thread(target=client, args=("b", 2), daemon=True)
    t_b.start()
    _await_live(ex, "b")
    ex.start()
    assert first_token.wait(timeout=120), "'a' never started decoding"
    assert ex.snapshot()["pending"] == 1      # "b": no slot, it waits
    ex.stop()
    t_a.join(timeout=120)
    t_b.join(timeout=120)
    assert not t_a.is_alive() and not t_b.is_alive(), \
        "stop() left a submitter/waiter hanging"
    assert "in flight" in errs.get("a", "")
    assert "in flight" in errs.get("b", "")
    # ... and so is every later submit
    with pytest.raises(RuntimeError, match="in flight"):
        ex.submit("c", jnp.zeros((1, 4), jnp.int32), 2)


def test_executor_worker_death_fails_current_and_later_waits():
    """A worker whose tick() raises marks the executor dead: the waiter of
    the request in flight raises with that error instead of hanging, and
    so does every later submit and wait (what /healthz answers 503 on)."""
    import threading

    import jax.numpy as jnp

    from pipeedge_tpu.parallel.batcher import ContinuousBatcher

    ex = ContinuousBatcher(_tiny_pipe(), max_active=1)

    def broken_token(step, tok):
        raise OSError("the device went away")

    ids = jnp.zeros((1, 4), jnp.int32)
    ex.submit("r", ids, 8, on_token=broken_token)
    quiet, threading.excepthook = threading.excepthook, lambda args: None
    try:                          # the worker re-raises as it dies: expected
        ex.start()
        with pytest.raises(RuntimeError, match="the device went away"):
            ex.wait("r", timeout=120)
        ex._worker.join(timeout=120)
    finally:
        threading.excepthook = quiet
    assert isinstance(ex.dead, OSError) and not ex._worker.is_alive()
    with pytest.raises(RuntimeError, match="the device went away"):
        ex.wait("never-submitted", timeout=120)
    with pytest.raises(RuntimeError, match="the device went away"):
        ex.submit("later", ids, 2)
    ex.stop()                     # a dead executor still stops cleanly


def test_eight_client_threads_match_run():
    """Eight threads submitting and waiting at once on the thread-driven
    executor get, each, the tokens `run()` gives the same requests."""
    import threading

    from pipeedge_tpu.parallel.batcher import ContinuousBatcher

    pipe = _tiny_pipe(partition=[(1, 4), (5, 8)])
    rng = np.random.default_rng(83)
    jobs = [(f"r{i}", rng.integers(0, 100, size=(1 + i % 2, 3 + i)),
             4 + i % 3, {} if i % 2 else {"temperature": 0.9, "seed": i})
            for i in range(8)]
    offline = ContinuousBatcher(pipe, max_active=3)
    for rid, ids, n, kw in jobs:
        offline.submit(rid, ids, n, **kw)
    want = offline.run()

    ex = ContinuousBatcher(pipe, max_active=3).start()
    got, errs = {}, []

    def client(rid, ids, n, kw):
        try:
            ex.submit(rid, ids, n, **kw)
            got[rid] = ex.wait(rid, timeout=300)
        except BaseException as exc:   # noqa: BLE001 — reported below
            errs.append((rid, exc))

    threads = [threading.Thread(target=client, args=job, daemon=True)
               for job in jobs]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
    finally:
        ex.stop()
    assert not errs, errs
    assert set(got) == set(want)
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    assert ex.dead is not None and not ex.live_rids()   # stopped, drained


@pytest.mark.parametrize("drive", ["run", "thread"])
def test_cancel_flag_completes_request_early(drive):
    """A set `cancel` flag finishes the request at its next pick with the
    tokens decoded so far, freeing executor capacity for live requests
    (the serve.py streaming-disconnect contract)."""
    import threading

    import jax.numpy as jnp

    from pipeedge_tpu.parallel.batcher import ContinuousBatcher

    pipe = _tiny_pipe()
    cancel = threading.Event()
    stop_after = 3
    seen = []

    def on_token(step, tok):
        seen.append(step)
        if step + 1 >= stop_after:
            cancel.set()

    ids = jnp.zeros((1, 4), jnp.int32)
    batcher = ContinuousBatcher(pipe, max_active=1)
    if drive == "thread":
        batcher.start()
    batcher.submit("r", ids, 40, on_token=on_token, cancel=cancel)
    try:
        out = (batcher.wait("r", timeout=120) if drive == "thread"
               else batcher.run()["r"])
    finally:
        batcher.stop()
    # prompt (4) + the tokens decoded before the cancel took effect —
    # far short of the 40-token cap
    assert out.shape[1] == 4 + stop_after
    assert len(seen) == stop_after


@pytest.fixture(scope="module")
def tight_server():
    """A server with a SINGLE admission slot: a dead request that failed
    to free its slot would block every later request."""
    yield from _spawn_server(("--max-active", "1"))


def test_streaming_disconnect_cancels_generation(tight_server):
    """A streaming client that disconnects mid-response must not keep
    decoding to the cap on a dead socket: the writer thread's failed send
    sets the request's cancel flag, the executor completes it early, and
    the admission slot frees. Verified via the server's
    cumulative token counter: the aborted 40-token request generates only
    a handful of tokens."""
    port = tight_server

    def healthz():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
            return json.loads(resp.read())["stats"]

    tokens_before = healthz()["tokens"]
    new_tokens = 40
    body = json.dumps({"ids": [[1, 2, 3]], "new_tokens": new_tokens,
                       "stream": True}).encode()
    with socket.create_connection(("127.0.0.1", port), timeout=60) as sock:
        sock.sendall(
            b"POST /generate HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
            + body)
        # read until two step lines arrived (the stream is live), then
        # vanish with an RST so the server's next chunk write fails fast
        buf = b""
        deadline = time.monotonic() + 120
        while buf.count(b'"step"') < 2:
            assert time.monotonic() < deadline, f"no stream lines: {buf!r}"
            chunk = sock.recv(4096)
            assert chunk, f"server closed early: {buf!r}"
            buf += chunk
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
    # the executor must finish the cancelled request and free its slot
    deadline = time.monotonic() + 120
    while healthz()["active"] > 0:
        assert time.monotonic() < deadline, \
            "cancelled request still holds its executor slot"
        time.sleep(0.1)
    generated = healthz()["tokens"] - tokens_before
    assert generated < new_tokens, (
        f"disconnected request decoded all {generated} tokens to the cap")
    # ... and the freed slot serves new requests normally
    out = _post(port, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2})
    assert len(out["ids"][0]) == 5


def test_streaming_disconnect_storm_does_not_exhaust_slots(tight_server):
    """A BURST of streaming clients that all vanish mid-response (N well
    past max_active=1) must not strand admission slots: every cancelled
    request retires, `active` returns to 0, and a fresh request admits
    promptly instead of queueing behind ghosts."""
    port = tight_server

    def healthz():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
            return json.loads(resp.read())["stats"]

    body = json.dumps({"ids": [[1, 2, 3]], "new_tokens": 40,
                       "stream": True}).encode()
    head = (b"POST /generate HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n")
    # open the whole storm first (they queue on the 1-slot executor),
    # then abort every socket with an RST — sockets still waiting for
    # admission AND the one mid-stream both disconnect
    socks = [socket.create_connection(("127.0.0.1", port), timeout=60)
             for _ in range(4)]
    try:
        for sock in socks:
            sock.sendall(head + body)
        # make sure at least one stream actually started before the storm
        # aborts (otherwise the test never exercises mid-flight cancel)
        buf, deadline = b"", time.monotonic() + 120
        while b'"step"' not in buf:
            assert time.monotonic() < deadline, f"no stream: {buf!r}"
            chunk = socks[0].recv(4096)
            assert chunk, f"server closed early: {buf!r}"
            buf += chunk
    finally:
        for sock in socks:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
            sock.close()
    # every ghost must retire and free its slot
    deadline = time.monotonic() + 120
    while healthz()["active"] > 0:
        assert time.monotonic() < deadline, (
            "disconnect storm stranded admission slots: active="
            f"{healthz()['active']}")
        time.sleep(0.1)
    # the server still serves: a fresh request admits through the single
    # slot the storm just vacated
    out = _post(port, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2})
    assert len(out["ids"][0]) == 5


def test_executor_stop_fails_live_waiters():
    """stop() with requests in flight fails their waiters instead of
    hanging them (code-review finding)."""
    import threading

    import jax.numpy as jnp

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    from pipeedge_tpu.parallel.batcher import ContinuousBatcher

    total = registry.get_model_layers(MODEL)
    _, params, _ = registry.module_shard_factory(MODEL, None, 1, total,
                                                 unroll=False)
    pipe = decode.DecodePipeline(
        registry.get_model_entry(MODEL).family.FAMILY,
        registry.get_model_config(MODEL), [(1, total)], [params],
        max_len=64)
    ex = ContinuousBatcher(pipe).start()
    errs = {}
    first_token = threading.Event()

    def slow_token(step, tok):
        first_token.set()
        time.sleep(0.05)

    def client():
        ex.submit("r", jnp.zeros((1, 4), jnp.int32), 55,
                  on_token=slow_token)
        try:
            ex.wait("r", timeout=120)
        except RuntimeError as exc:
            errs["r"] = str(exc)

    t = threading.Thread(target=client)
    t.start()
    # in the pipeline for certain, and seconds from its end
    assert first_token.wait(timeout=120)
    ex.stop()
    t.join(timeout=120)
    assert not t.is_alive()
    assert "in flight" in errs.get("r", "")


def test_degraded_window_503_retry_after_and_healthz(server):
    """The failover window (POST /degraded): /healthz names the dead rank,
    new work is answered 503 with a Retry-After header, and clearing the
    window restores normal service."""
    port = server
    try:
        assert _post(port, "/degraded", {"degraded": True, "dead_rank": 1,
                                         "retry_after": 2})["degraded"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
            health = json.loads(resp.read())
        assert health["ok"]                      # degraded, not dead
        assert health["degraded"]["dead_rank"] == 1
        assert health["degraded"]["retry_after"] == 2
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(port, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2})
        assert err.value.code == 503
        assert err.value.headers["Retry-After"] == "2"
        body = json.loads(err.value.read())
        assert body["degraded"] and body["dead_rank"] == 1
        # prefix registration is admission too
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(port, "/prefix", {"ids": [1, 2, 3]})
        assert err.value.code == 503
    finally:
        _post(port, "/degraded", {"degraded": False})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        assert json.loads(resp.read())["degraded"] is False
    out = _post(port, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2})
    assert len(out["ids"][0]) == 5


def test_degraded_healing_healed_lifecycle(server):
    """The heal-aware window lifecycle: degraded -> healing (rank
    rejoined; still refusing with Retry-After, but /healthz distinguishes
    the phase) -> healed ({"degraded": false, "healed": true} clears the
    window AND counts on rejoined_ranks_total / /metrics)."""
    port = server

    def health():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
            return json.loads(resp.read())

    before = health()["stats"]["rejoined_ranks_total"]
    try:
        _post(port, "/degraded", {"degraded": True, "dead_rank": 1,
                                  "retry_after": 2})
        assert health()["degraded"]["phase"] == "degraded"
        # the rank rejoined; the orchestrator flips the window to healing
        _post(port, "/degraded", {"degraded": True, "healing": True})
        h = health()
        assert h["degraded"]["phase"] == "healing"
        assert h["degraded"]["dead_rank"] == 1   # window state preserved
        # still refusing admission while the heal is in flight
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(port, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2})
        assert err.value.code == 503
    finally:
        # capacity restored: the healed close clears the window and bumps
        # the rejoined counter on BOTH surfaces
        _post(port, "/degraded", {"degraded": False, "healed": True,
                                  "rank": 1})
    h = health()
    assert h["degraded"] is False
    assert h["stats"]["rejoined_ranks_total"] == before + 1
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        text = resp.read().decode()
    assert "pipeedge_serve_rejoined_ranks_total" in text
    # a stray healing signal with no window open must not resurrect one
    _post(port, "/degraded", {"degraded": True, "healing": True})
    assert health()["degraded"] is False
    # and a plain (non-healed) clear does not count as a rejoin
    _post(port, "/degraded", {"degraded": True, "dead_rank": 2})
    _post(port, "/degraded", {"degraded": False})
    assert health()["stats"]["rejoined_ranks_total"] == before + 1
    out = _post(port, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2})
    assert len(out["ids"][0]) == 5


def test_degraded_in_flight_request_replayed(solo_pipe):
    """A request that was IN FLIGHT when the failover window opened and
    whose executor fails during it is replayed once after recovery — the
    client sees one clean result, not the transient."""
    import threading

    from tools import serve as serve_mod

    svc = serve_mod._Service(solo_pipe)
    try:
        calls = []
        orig = svc._generate_once

        def flaky(ids, new_tokens, on_token, kw, rid=None):
            if not calls:
                calls.append(1)
                # the stage dies under this request: the service degrades
                # and the executor surfaces a transient failure
                svc.enter_degraded(dead_rank=1, retry_after=5.0)
                raise RuntimeError("stage died under this request")
            return orig(ids, new_tokens, on_token, kw, rid=rid)

        svc._generate_once = flaky
        recover = threading.Timer(0.5, svc.exit_degraded)
        recover.start()
        out = np.asarray(svc.generate([[5, 6, 7]], 3))
        recover.join()
        assert calls == [1]              # failed once, replayed once
        want = np.asarray(solo_pipe.generate(np.asarray([[5, 6, 7]]), 3))
        np.testing.assert_array_equal(out, want)
        # admission during a (re-entered) window still refuses new work
        svc.enter_degraded(dead_rank=2, retry_after=1.0)
        with pytest.raises(serve_mod.ServiceDegraded):
            svc.generate([[5, 6, 7]], 2)
        svc.exit_degraded()
    finally:
        svc.stop()


def _get_text(port, path, timeout=30):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as resp:
        return resp.headers.get("Content-Type", ""), resp.read().decode()


_PROM_LINE_RE = __import__("re").compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r" [-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|Inf|NaN)$")


def test_metrics_endpoint_prometheus(server):
    """GET /metrics: Prometheus text format with the request-latency
    histogram, per-edge wire-byte counters, and the degraded/failover
    history — and /healthz's stats agree with it (one source of truth)."""
    port = server
    _post(port, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2})
    ctype, text = _get_text(port, "/metrics")
    assert ctype.startswith("text/plain")
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert _PROM_LINE_RE.match(line), f"bad line: {line!r}"
    # request metrics present and live
    assert "# TYPE pipeedge_serve_request_latency_seconds histogram" in text
    assert "pipeedge_serve_request_latency_seconds_count" in text
    assert 'pipeedge_serve_requests_total{endpoint="/generate",' \
           'status="200"}' in text
    # per-edge wire-byte counters: the 2-stage server has one edge,
    # pre-declared so it renders even before traffic, nonzero after
    assert 'pipeedge_serve_edge_wire_bytes_total{edge="0->1"}' in text
    edge_val = [line for line in text.splitlines()
                if line.startswith('pipeedge_serve_edge_wire_bytes_total')]
    assert any(float(line.rsplit(" ", 1)[1]) > 0 for line in edge_val)
    # degraded/failover history starts clean and matches healthz
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        stats = json.loads(resp.read())["stats"]
    assert "pipeedge_serve_degraded_entered_total" in text
    assert {"degraded_entered_total", "failover_replays_total",
            "last_dead_rank"} <= set(stats)
    # open+close a degraded window: both surfaces move together
    _post(port, "/degraded", {"degraded": True, "dead_rank": 3,
                              "retry_after": 1})
    _post(port, "/degraded", {"degraded": False})
    _, text2 = _get_text(port, "/metrics")
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        stats2 = json.loads(resp.read())["stats"]
    assert stats2["degraded_entered_total"] == \
        stats["degraded_entered_total"] + 1
    assert stats2["last_dead_rank"] == 3
    assert "pipeedge_serve_last_dead_rank 3" in text2


# ---------------------------------------------------------------------------
# paged KV plane + disaggregated serving over HTTP (docs/SERVING.md)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def kv_server():
    """A paged, DISAGGREGATED server: --kv-pages turns admission into a
    token budget and the prefix trie on; --disaggregate wire routes
    every prompt pass through the prefill fleet + the v2-codec loopback
    socket ship path."""
    yield from _spawn_server(("--kv-pages", "48", "--kv-page-size", "4",
                              "--disaggregate", "wire"))


def test_kv_server_tokens_match_solo_and_budget_visible(kv_server,
                                                        solo_pipe):
    port = kv_server
    rng = np.random.default_rng(21)
    ids = rng.integers(0, 100, size=(1, 7)).tolist()
    got = _post(port, "/generate", {"ids": ids, "new_tokens": 6})["ids"]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(solo_pipe.generate(np.asarray(ids), 6)))
    # sampled too: the pick happens decode-side from shipped logits,
    # so the rng discipline matches solo exactly
    got_s = _post(port, "/generate", {"ids": ids, "new_tokens": 5,
                                      "temperature": 0.9, "seed": 4})["ids"]
    np.testing.assert_array_equal(
        np.asarray(got_s),
        np.asarray(solo_pipe.generate(np.asarray(ids), 5,
                                      temperature=0.9, seed=4)))
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        serving = json.loads(resp.read())["serving"]
    kv = serving["kv"]
    assert kv["disaggregated"] and kv["pool"]["pages_total"] == 48
    # idle server: every page is back (free + trie-cached)
    assert kv["pool"]["pages_free"] \
        + kv["prefix"]["pages_cached"] == 48
    adm = serving["admission"]
    assert adm["token_budget"] == 48 * 4
    assert adm["tokens_free"] == adm["token_budget"]


def test_kv_server_prefix_id_rides_the_trie(kv_server, solo_pipe):
    """Paged mode /prefix: registration is a token list; generate with
    prefix_id returns suffix+continuation exactly like the dense handle
    contract, token-identical to a solo full-prompt run."""
    port = kv_server
    rng = np.random.default_rng(33)
    prefix = rng.integers(0, 100, size=(8,)).tolist()
    reg = _post(port, "/prefix", {"ids": prefix})
    assert reg["len"] == 8
    suffix = rng.integers(0, 100, size=(1, 3)).tolist()
    full = np.asarray([prefix + suffix[0]])
    want = np.asarray(solo_pipe.generate(full, 5))[:, 8:]
    for _ in range(2):      # the second run reuses decode-side pages
        got = _post(port, "/generate",
                    {"ids": suffix, "new_tokens": 5,
                     "prefix_id": reg["prefix_id"]})["ids"]
        np.testing.assert_array_equal(np.asarray(got), want)
    # unknown prefix ids stay clean 400s in paged mode
    try:
        _post(port, "/generate", {"ids": suffix, "new_tokens": 2,
                                  "prefix_id": "nope"})
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as exc:
        assert exc.code == 400


def test_kv_server_streaming_and_metrics(kv_server):
    port = kv_server
    body = json.dumps({"ids": [[1, 2, 3, 4, 5]], "new_tokens": 4,
                       "stream": True}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        lines = [json.loads(line) for line in
                 resp.read().decode().strip().splitlines()]
    assert lines[-1]["steps"] == 4 and len(lines) == 5
    assert len(lines[-1]["ids"][0]) == 5 + 4
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        text = resp.read().decode()
    for family in ("pipeedge_kv_pages", "pipeedge_kv_prefix_lookups_total",
                   "pipeedge_kv_ship_bytes_total",
                   "pipeedge_admission_tokens_free"):
        assert family in text, family
    # the wire ship path actually moved bytes
    wire_line = [line for line in text.splitlines()
                 if line.startswith('pipeedge_kv_ship_bytes_total{path="wire"}')]
    assert wire_line and float(wire_line[0].rsplit(" ", 1)[1]) > 0


def test_chunked_prefill_without_kv_pages_rejected_at_parse_time():
    """--chunked-prefill without --kv-pages is refused AT PARSE TIME,
    in milliseconds, with both flags named (ISSUE 16 satellite: chunk
    waves write prompt spans at an offset into a page table — dense
    slots have no such path)."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "-m", MODEL, "--chunked-prefill", "8",
         "--port", str(_free_port())],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    took = time.monotonic() - t0
    assert proc.returncode == 2          # argparse usage error
    assert "--chunked-prefill" in proc.stderr \
        and "--kv-pages" in proc.stderr
    # parse-time means no model was built (interpreter startup only)
    assert took < 30, f"flag validation took {took:.1f}s — a model build?"


def test_prefill_budget_without_chunked_rejected_at_parse_time():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "-m", MODEL, "--kv-pages", "8", "--prefill-budget", "4",
         "--port", str(_free_port())],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert proc.returncode == 2
    assert "--prefill-budget" in proc.stderr \
        and "--chunked-prefill" in proc.stderr


def test_disaggregate_without_kv_pages_rejected_at_parse_time():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "-m", MODEL, "--disaggregate", "process",
         "--port", str(_free_port())],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert proc.returncode == 2
    assert "--disaggregate" in proc.stderr and "--kv-pages" in proc.stderr


# ---------------------------------------------------------------------------
# continuous batching + chunked prefill + paged speculative (ISSUE 16)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chunked_server():
    """Iteration-level scheduling on: prompts longer than 6 tokens run
    as 6-token chunk waves interleaved with decode steps, and the
    admission queue is re-driven at every step boundary."""
    yield from _spawn_server(("--kv-pages", "48", "--kv-page-size", "4",
                              "--chunked-prefill", "6", "--step-join"))


def test_chunked_server_tokens_match_solo(chunked_server, solo_pipe):
    """Long prompts served through chunked prefill are token-identical
    to the solo pipeline, and the healthz scheduler block proves chunk
    waves actually ran."""
    port = chunked_server
    rng = np.random.default_rng(57)
    for plen, nt, kw in ((20, 6, {}), (17, 5, {"temperature": 0.8,
                                               "seed": 3})):
        ids = rng.integers(0, 100, size=(1, plen)).tolist()
        got = _post(port, "/generate",
                    {"ids": ids, "new_tokens": nt, **kw})["ids"]
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(solo_pipe.generate(np.asarray(ids), nt, **kw)))
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        serving = json.loads(resp.read())["serving"]
    sched = serving["scheduler"]
    assert sched["chunked_prefill"] == 6 and sched["step_join"] is True
    assert sched["chunk_tokens"] == 6      # brownout lever unarmed
    assert sched["prefill_chunks"] >= 2    # both prompts chunked
    # idle: every page back (free + trie-cached)
    kv = serving["kv"]
    assert kv["pool"]["pages_free"] + kv["prefix"]["pages_cached"] == 48


@pytest.fixture(scope="module")
def spec_kv_server():
    """--draft-model + --kv-pages now compose (ISSUE 16): speculative
    draft/verify caches are paged onto the pool plane — the target's
    rounds reserve from the decode pool, the draft from its own."""
    yield from _spawn_server(("--kv-pages", "48", "--kv-page-size", "4",
                              "--draft-model", MODEL, "--gamma", "2"))


def test_speculative_over_paged_kv_matches_plain(spec_kv_server,
                                                 solo_pipe):
    port = spec_kv_server
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        assert json.loads(resp.read())["speculative"] is True
    rng = np.random.default_rng(41)
    ids = rng.integers(0, 100, size=(1, 7)).tolist()
    want = np.asarray(solo_pipe.generate(np.asarray(ids), 6))
    got = _post(port, "/generate", {"ids": ids, "new_tokens": 6,
                                    "speculative": True})["ids"]
    np.testing.assert_array_equal(np.asarray(got), want)
    # plain requests share the same pool and stay identical too
    got_p = _post(port, "/generate", {"ids": ids, "new_tokens": 6})["ids"]
    np.testing.assert_array_equal(np.asarray(got_p), want)
    # idle: the speculative rounds returned every page they reserved
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        kv = json.loads(resp.read())["serving"]["kv"]
    assert kv["pool"]["pages_free"] + kv["prefix"]["pages_cached"] == 48
