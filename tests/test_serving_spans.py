"""The serving loop under the one probe (docs/OBSERVABILITY.md): the decode
executor's phases as `exec` spans, the writer thread's flush, read-back and
write, the span digest and the compile counters on /metrics, stable names for
the jitted stage programs, and the profiler hook."""
import json
import statistics
import threading
import time
import urllib.error
import urllib.request
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import prom as bench_prom
from pipeedge_tpu import telemetry
from pipeedge_tpu.parallel import decode
from pipeedge_tpu.parallel.batcher import ContinuousBatcher
from pipeedge_tpu.telemetry import metrics as prom
from pipeedge_tpu.utils import tracing
from test_serve import _spawn_server
from test_tracing import _host_events, _newest_xplane

MODEL = "pipeedge/test-tiny-gpt2"
REQUESTS, NEW_TOKENS = 3, 8


@pytest.fixture(scope="module")
def pipe():
    return decode.build_decode_pipeline(MODEL, max_len=48)


def _prompts():
    rng = np.random.default_rng(5)
    return [np.asarray(rng.integers(0, 100, size=(1, 6)), np.int64)
            for _ in range(REQUESTS)]


def _run_thread(pipe, tag):
    """The served way: the executor's own worker thread ticks."""
    batcher = ContinuousBatcher(pipe, max_active=REQUESTS).start()
    try:
        for i, ids in enumerate(_prompts()):
            batcher.submit(f"{tag}{i}", ids, new_tokens=NEW_TOKENS)
        for i in range(REQUESTS):
            batcher.wait(f"{tag}{i}", timeout=120)
    finally:
        batcher.stop()


def _run_offline(pipe, tag):
    """The offline way: the caller's thread ticks (`run()`)."""
    batcher = ContinuousBatcher(pipe, max_active=REQUESTS)
    for i, ids in enumerate(_prompts()):
        batcher.submit(f"{tag}{i}", ids, new_tokens=NEW_TOKENS)
    batcher.run()


@pytest.mark.parametrize("run", [_run_thread, _run_offline],
                         ids=["thread", "run"])
def test_executor_worker_is_always_inside_a_named_span(pipe, run):
    """One `stage/exec0` a dispatch: a prompt pass a request (tagged with
    it, as its `exec/seed`, `exec/install` and `exec/pick` are), then one
    for every
    step of the rows that stand at it together (no request's tag; at most
    a token's worth, seven where all three step from the first step on, as
    they do when the caller submits before it ticks). One `exec/retire` a
    request, tagged; one `exec/read` a tick that brought tokens and, since
    a tick's tokens leave in one hand-over, one `exec/emit` behind it for
    all the rows it brought (no request's tag: a token's worth at least,
    never one a token a request). The spans of the one worker
    never overlap, and what lies between two of them is book-keeping only
    (that no dispatch hides there is the next test's to say)."""
    run(pipe, "warm")                   # compile outside the measurement
    rec = telemetry.configure()
    try:
        run(pipe, "r")
    finally:
        telemetry.disable()
    spans = sorted((s for s in rec.snapshot()
                    if s["cat"] in ("stage", "exec")),
                   key=lambda s: s["t0"])
    tokens = REQUESTS * NEW_TOKENS
    counts = Counter((s["cat"], s["name"]) for s in spans
                     if s["name"] not in ("wait0", "admit"))
    steps = counts.pop(("stage", "exec0")) - REQUESTS
    reads = counts.pop(("exec", "read"))
    assert NEW_TOKENS - 1 <= steps <= tokens - REQUESTS
    assert run is _run_thread or steps == NEW_TOKENS - 1
    emits = counts.pop(("exec", "emit"))
    assert 1 <= reads <= steps + REQUESTS + 1
    assert NEW_TOKENS <= emits <= reads
    assert counts == {("exec", "seed"): REQUESTS,
                      ("exec", "install"): REQUESTS,
                      ("exec", "pick"): REQUESTS,
                      ("exec", "retire"): REQUESTS}
    assert not any(s.get("rid") for s in spans if s["name"] == "emit")
    for name in ("exec0", "seed", "install", "pick", "retire"):
        per_request = Counter(s["rid"] for s in spans if s["name"] == name)
        per_request.pop(None, None)     # the steps of all rows
        assert set(per_request) == {f"r{i}" for i in range(REQUESTS)}
    assert all(a["t1"] <= b["t0"] for a, b in zip(spans, spans[1:]))

    # coverage between the first and the last span, from medians: a
    # preempted thread (six test workers share this machine) stretches a
    # few gaps or spans a hundredfold, and a median does not notice
    gaps, lengths = {}, {}
    for a, b in zip(spans, spans[1:]):
        gaps.setdefault((a["name"], b["name"]), []).append(b["t0"] - a["t1"])
    for s in spans:
        lengths.setdefault(s["name"], []).append(s["t1"] - s["t0"])
    unnamed = sum(statistics.median(v) * len(v) for v in gaps.values())
    named = sum(statistics.median(v) * len(v) for v in lengths.values())
    assert max(statistics.median(v) for v in gaps.values()) < 100_000
    # ISSUE 24 asks for 95%, which a token's two dispatches of 0.6 ms on
    # the chip clear by far (PERF.md section 6); here they are 0.2 ms and
    # the 5-10 us between two spans, four times a token, are 12-15%
    assert named / (named + unnamed) >= 0.80


def _run_generate(pipe, tag):
    jax.block_until_ready(pipe.generate(_prompts()[0], NEW_TOKENS))


@pytest.mark.parametrize("run, step, pick", [
    (_run_thread, "stage/exec0", "exec/pick"),
    (_run_offline, "stage/exec0", "exec/pick"),
    (_run_generate, "generate/prefill", "generate/pick")],
    ids=["thread", "run", "generate"])
def test_a_token_is_a_stage_program_and_a_pick_and_nothing_eager(
        pipe, tmp_path, run, step, pick):
    """What the profiler sees the decoding thread dispatch, from its first
    stage program to its last pick. `generate`: `prefill`, `decode_step` and
    `pick_next`, one pick a stage program, and the one `slice` that cuts a
    prompt pass's last position out, once a batch. The executor: a prompt
    pass is the zeros of the cache it fills (`exec/seed`: a
    `broadcast_in_dim`, and a `convert_element_type` where the dtype asks),
    `prefill`, its `slice` and `pick_next`, then `install_rows` and
    `join_ids` once a request; a step of the rows that stand together is
    `rows_step` alone, the pick inside it. An eager slice, split, cast or
    reshape of a step's token would be a `PjitFunction` of its own here, as
    it is a dispatch of its own on the chip (PERF.md section 6, PR 28)."""
    run(pipe, "warm")                   # compile outside the trace
    with tracing.trace(str(tmp_path)):
        run(pipe, "r")
    from jax.profiler import ProfileData
    (line,) = [sorted(line.events, key=lambda e: e.start_ns)
               for plane in ProfileData.from_file(
                   _newest_xplane(str(tmp_path))).planes
               if not plane.name.startswith("/device:")
               for line in plane.lines
               if any(e.name == step for e in line.events)]
    t0 = next(e.start_ns for e in line if e.name == step)
    t1 = max(e.start_ns + e.duration_ns for e in line
             if e.name in (step, pick))
    programs = Counter(e.name for e in line if t0 <= e.start_ns < t1
                       and e.name.startswith("PjitFunction("))
    if run is _run_generate:
        assert set(programs) == {
            "PjitFunction(prefill)", "PjitFunction(slice)",
            "PjitFunction(decode_step)", "PjitFunction(pick_next)"}
        assert programs["PjitFunction(pick_next)"] == (
            programs["PjitFunction(prefill)"]
            + programs["PjitFunction(decode_step)"])
    else:
        seed = {"PjitFunction(broadcast_in_dim)",
                "PjitFunction(convert_element_type)"}
        assert set(programs) - seed == {
            "PjitFunction(prefill)", "PjitFunction(slice)",
            "PjitFunction(pick_next)", "PjitFunction(install_rows)",
            "PjitFunction(join_ids)", "PjitFunction(rows_step)"}
        # the profiler may show a call more than once: count in requests
        each = programs["PjitFunction(prefill)"] // REQUESTS
        for once in ("pick_next", "install_rows", "join_ids"):
            assert programs[f"PjitFunction({once})"] == each * REQUESTS
        # a cache's two leaves a request, never a step's token
        assert sum(programs[name] for name in seed) <= 4 * each * REQUESTS, \
            programs
        assert each * (NEW_TOKENS - 1) <= programs[
            "PjitFunction(rows_step)"] <= each * REQUESTS * (NEW_TOKENS - 1)
    assert programs["PjitFunction(slice)"] == programs["PjitFunction(prefill)"]


def test_worker_waits_in_a_span_of_its_own(pipe):
    """The executor's worker with nothing to do sits in `exec/wait0`: its
    idle time is named, and is a digest row like every phase."""
    rec = telemetry.configure()
    try:
        executor = ContinuousBatcher(pipe, max_active=1).start()
        time.sleep(0.05)
        executor.stop()
    finally:
        telemetry.disable()
    (wait,) = [s for s in rec.snapshot() if s["cat"] == "exec"]
    assert (wait["name"], wait["stage"]) == ("wait0", 0)
    assert wait["t1"] - wait["t0"] >= 40_000_000
    assert rec.digest()[("exec", "wait0", 0)] == (1, wait["t1"] - wait["t0"])


@pytest.mark.parametrize("program, name", [("prefill", "jit_prefill"),
                                           ("decode", "jit_decode_step")])
def test_stage_programs_have_stable_module_names(pipe, program, name):
    """What a profiler trace (and the ledger's breakdown) calls the
    server's programs: never `jit__unknown`."""
    stage = pipe.stages[0]
    cache = pipe._fresh_caches(1)[0]
    ids = jnp.zeros((1, 6), jnp.int32)
    lowered = (stage["prefill"].lower(stage["params"], ids, cache)
               if program == "prefill"
               else stage["decode"].lower(stage["params"], ids[:, :1], cache,
                                          6, read_len=64))
    assert lowered.as_text().startswith(f"module @{name} ")


def test_host_pipeline_stage_program_is_named_host_stage_step():
    from pipeedge_tpu.parallel.pipeline import PipelineStage
    stage = PipelineStage(lambda params, x: x * params["w"],
                          {"w": jnp.ones(())}, jax.devices()[0])
    text = stage._fn_for_bit(0).lower(stage.params,
                                      jnp.ones((2, 2))).as_text()
    assert text.startswith("module @jit_host_stage_step ")


def test_compile_counter_moves_on_a_new_shape_and_not_on_a_repeat():
    registry = prom.Registry()
    compiles, seconds = prom.count_jax_compiles(registry)
    step = jax.jit(lambda x: x * 3 + 1)
    first = jnp.ones((5,), jnp.float32)
    other = jnp.ones((7,), jnp.float32)
    jax.block_until_ready(step(first))
    after_first, spent = compiles.value(), seconds.value()
    assert after_first >= 1 and spent > 0
    jax.block_until_ready(step(first))
    assert compiles.value() == after_first      # a warm shape: no event
    jax.block_until_ready(step(other))
    assert compiles.value() == after_first + 1  # a new shape: exactly one
    assert seconds.value() > spent
    text = registry.render()
    assert f"pipeedge_jax_compiles_total {int(after_first + 1)}" in text
    assert "pipeedge_jax_compile_seconds_total " in text


def test_span_digest_renders_as_two_counter_families():
    rec = telemetry.SpanRecorder()
    rec.record("stage", "exec0", 1_000, 3_500_000_000, stage=0)
    rec.record("stage", "exec0", 0, 1, stage=0)
    rec.record("exec", "pick", 10, 20)
    rec.record("sched", "join", 5, 5)           # not a digest category
    lines = prom.render_span_digest(rec.digest())
    assert 'pipeedge_span_seconds_total{cat="stage",name="exec0",' \
        'stage="0"} 3.499999001' in lines
    assert 'pipeedge_span_count_total{cat="stage",name="exec0",' \
        'stage="0"} 2' in lines
    assert 'pipeedge_span_seconds_total{cat="exec",name="pick",' \
        'stage=""} 0.000000010' in lines
    assert not any("sched" in line for line in lines)
    assert sum(line.startswith("# TYPE") for line in lines) == 2


# -- against a live server ------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=60) as resp:
        return resp.read().decode()


def _post(port, path, obj=None, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(obj or {}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode()


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    profile_dir = str(tmp_path_factory.mktemp("profile"))
    for port in _spawn_server(["--profile-dir", profile_dir]):
        yield port, profile_dir


def _family(text, family):
    """{(cat, name, stage): value} of one digest family on /metrics."""
    return {(labels["cat"], labels["name"], labels["stage"]): value
            for labels, value in bench_prom.samples(text, family)}


@pytest.mark.fleet
def test_metrics_digest_is_monotone_and_equals_the_rings_sums(server):
    """One instrumentation site, three readers: what /metrics says of a
    phase is what its spans in the ring add up to, and it only grows."""
    port, _ = server
    _get(port, "/debug/spans")                  # empty the ring
    start = _get(port, "/metrics")
    for seed in range(2):
        out = _post(port, "/generate", {"ids": [[3, 4, 5, 6]],
                                        "new_tokens": 5, "stream": True,
                                        "seed": seed})
        assert len(out.strip().splitlines()) == 6      # 5 tokens, 1 result
    middle = _get(port, "/metrics")
    _post(port, "/generate", {"ids": [[7, 8, 9]], "new_tokens": 3})
    end = _get(port, "/metrics")
    ring = json.loads(_get(port, "/debug/spans"))
    assert ring["dropped"] == 0

    for family in ("pipeedge_span_seconds_total", "pipeedge_span_count_total"):
        a, b, c = (_family(text, family) for text in (start, middle, end))
        assert set(a) <= set(b) <= set(c)
        assert all(a[k] <= b[k] for k in a) and all(b[k] <= c[k] for k in b)
    count = _family(end, "pipeedge_span_count_total")
    seconds = _family(end, "pipeedge_span_seconds_total")
    count0 = _family(start, "pipeedge_span_count_total")
    seconds0 = _family(start, "pipeedge_span_seconds_total")
    # streamed tokens cross the writer thread's two spans once each,
    # inside the one `serve/flush` of the hand-over that brought them
    assert count[("serve", "readback", "")] \
        - count0.get(("serve", "readback", ""), 0) == 10
    assert count[("serve", "write", "")] \
        - count0.get(("serve", "write", ""), 0) == 10
    assert 1 <= count[("serve", "flush", "")] \
        - count0.get(("serve", "flush", ""), 0) <= 10
    assert count[("stage", "exec0", "0")] \
        - count0.get(("stage", "exec0", "0"), 0) == 13
    assert ("exec", "wait0", "0") in count
    # the digest's gain over the three requests is the ring's sum, to the
    # nanosecond (`exec/wait0` aside: one is open across either scrape)
    sums = {}
    for span in ring["spans"]:
        if span["cat"] in telemetry.DIGEST_CATEGORIES:
            key = (span["cat"], span["name"],
                   "" if span["stage"] is None else str(span["stage"]))
            n, ns = sums.get(key, (0, 0))
            sums[key] = (n + 1, ns + span["t1"] - span["t0"])
    assert {("stage", "exec0", "0"), ("exec", "pick", ""),
            ("exec", "emit", ""), ("exec", "retire", ""),
            ("serve", "readback", ""), ("serve", "write", ""),
            ("serve", "flush", "")} <= set(sums)
    for key, (n, ns) in sums.items():
        if key == ("exec", "wait0", "0"):
            continue
        assert count[key] - count0.get(key, 0) == n, key
        assert round((seconds[key] - seconds0.get(key, 0.0)) * 1e9) == ns, key
    assert "pipeedge_jax_compiles_total " in end


@pytest.mark.fleet
def test_debug_profile_writes_a_trace_that_names_the_serving_loop(server):
    """POST /debug/profile: one session at a time (409 for a second), the
    seconds capped, and the trace it leaves names the executor's and the
    HTTP and writer threads' phases on the profiler's clock."""
    port, profile_dir = server
    _post(port, "/generate", {"ids": [[1, 2, 3, 4]], "new_tokens": 4,
                              "stream": True})    # warm: compile nothing
    answers = {}

    def profile():
        answers["first"] = json.loads(_post(port, "/debug/profile?seconds=3"))

    first = threading.Thread(target=profile)
    first.start()
    time.sleep(1.0)
    with pytest.raises(urllib.error.HTTPError) as refused:
        _post(port, "/debug/profile?seconds=1")
    assert refused.value.code == 409
    _post(port, "/generate", {"ids": [[1, 2, 3, 4]], "new_tokens": 4,
                              "stream": True})
    first.join(timeout=120)
    assert not first.is_alive()
    assert answers["first"] == {"path": profile_dir, "seconds": 3.0}

    names = _host_events(profile_dir)
    assert {"stage/exec0", "exec/pick", "exec/emit", "exec/retire",
            "exec/wait0", "serve/readback", "serve/write", "serve/flush",
            "serve/generate"} <= names
    with pytest.raises(urllib.error.HTTPError) as bad:
        _post(port, "/debug/profile?seconds=0")
    assert bad.value.code == 400


@pytest.mark.fleet
def test_debug_profile_is_off_without_a_directory():
    for port in _spawn_server():
        with pytest.raises(urllib.error.HTTPError) as off:
            _post(port, "/debug/profile?seconds=1")
        assert off.value.code == 404
