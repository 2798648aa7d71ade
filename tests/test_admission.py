"""Overload-protection plane (docs/SERVING.md): admission control,
deadline propagation, and the brownout ladder.

Units cover the mechanisms in isolation (token bucket, EDF queue,
service-rate estimator, admission controller, ladder hysteresis,
executor deadline-expiry cancellation); the fleet tests prove the wired
plane under fire — 5x sustained overload on a loopback serve.py keeps
interactive goodput while shedding the excess with 503 + a DYNAMIC
Retry-After, expires work mid-flight via the executor `cancel` flag
(HTTP 504), steps the brownout ladder up and back down, and survives an
overload window that overlaps a rank death (the /degraded lifecycle the
chaos orchestrator drives) without deadlock.
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "pipeedge/test-tiny-gpt2"

from pipeedge_tpu.serving import (AdmissionController, AdmissionShed,  # noqa: E402
                                  BrownoutLadder, EDFQueue,
                                  ServiceRateEstimator, TokenBucket,
                                  Watermarks, default_policies)
from pipeedge_tpu.telemetry import metrics as prom  # noqa: E402


# ---------------------------------------------------------------------------
# token bucket
# ---------------------------------------------------------------------------

def test_token_bucket_refill_and_burst_cap():
    b = TokenBucket(rate=2.0, burst=4.0, now=0.0)
    # burst capacity available immediately
    for _ in range(4):
        assert b.try_take(now=0.0)
    assert not b.try_take(now=0.0)           # empty
    assert b.try_take(now=0.5)               # 0.5s * 2/s = 1 token back
    assert not b.try_take(now=0.5)
    # refill never exceeds burst
    assert b.try_take(now=100.0)
    assert b.tokens == pytest.approx(3.0)

    with pytest.raises(ValueError):
        TokenBucket(rate=0.0, burst=1.0)
    with pytest.raises(ValueError):
        TokenBucket(rate=1.0, burst=0.0)


def test_default_policies_reject_zero_rate():
    # 0 must not silently mean "unlimited" (shed via brownout to block)
    with pytest.raises(ValueError, match="rate must be > 0"):
        default_policies(rates={"best_effort": 0.0})
    assert default_policies(rates={"batch": 2.5})["batch"].rate == 2.5


# ---------------------------------------------------------------------------
# EDF queue
# ---------------------------------------------------------------------------

def test_edf_queue_pops_earliest_deadline_first():
    q = EDFQueue(capacity=8)
    q.push("d5", 5.0)
    q.push("d1", 1.0)
    q.push("forever", None)                  # None sorts last
    q.push("d3", 3.0)
    assert len(q) == 4
    assert [q.pop()[0] for _ in range(4)] == ["d1", "d3", "d5", "forever"]
    assert q.pop() is None


def test_edf_queue_shed_on_full_evicts_latest_deadline():
    q = EDFQueue(capacity=2)
    assert q.push("a", 5.0) is None
    assert q.push("b", 1.0) is None
    # full: the new earlier-deadline arrival evicts the latest ("a")
    assert q.push("c", 3.0) == "a"
    # full: an arrival that IS the latest deadline is itself shed
    assert q.push("worst", 10.0) == "worst"
    assert q.push("never", None) == "never"  # None = latest of all
    assert [q.pop()[0] for _ in range(2)] == ["b", "c"]

    with pytest.raises(ValueError):
        EDFQueue(capacity=0)


def test_edf_queue_pop_expired_and_remove():
    q = EDFQueue(capacity=8)
    q.push("old", 1.0)
    q.push("older", 0.5)
    q.push("live", 9.0)
    q.push("forever", None)
    assert sorted(q.pop_expired(now=2.0)) == ["old", "older"]
    assert len(q) == 2
    assert q.remove("live")
    assert not q.remove("live")              # already gone
    assert q.pop()[0] == "forever"


# ---------------------------------------------------------------------------
# service-rate estimator (the dynamic Retry-After)
# ---------------------------------------------------------------------------

def test_service_rate_estimator_rate_and_retry_after():
    est = ServiceRateEstimator(halflife_s=10.0)
    assert est.rate() is None
    assert est.retry_after(3, fallback=7.0) == 7.0   # no data yet: fallback
    for t in (0.0, 1.0, 2.0, 3.0):
        est.observe(now=t)
    assert est.rate() == pytest.approx(1.0, rel=0.05)
    # "come back when the backlog you'd join has drained": (4+1)/1 = 5s
    assert est.retry_after(4) == pytest.approx(5.0, rel=0.05)
    # clamped at both ends
    assert est.retry_after(0, lo=2.0) == 2.0
    assert est.retry_after(10_000, hi=60.0) == 60.0


def test_percentile_from_counts_window_math():
    buckets = (0.1, 1.0, 10.0)
    assert prom.percentile_from_counts(buckets, [5, 4, 1], 10, 50.0) == 0.1
    assert prom.percentile_from_counts(buckets, [5, 4, 1], 10, 95.0) == 10.0
    # observations beyond the last bound live only in n: overflow -> inf
    assert prom.percentile_from_counts(buckets, [1, 0, 0], 5, 95.0) \
        == float("inf")
    assert prom.percentile_from_counts(buckets, [0, 0, 0], 0, 95.0) is None


# ---------------------------------------------------------------------------
# admission controller
# ---------------------------------------------------------------------------

def _controller(**kw):
    kw.setdefault("registry", prom.Registry())
    return AdmissionController(**kw)


def test_admission_immediate_grant_and_release():
    c = _controller(concurrency=2)
    t1 = c.admit("interactive")
    t2 = c.admit("batch")
    assert c.in_flight == 2 and c.queue_depth == 0
    with pytest.raises(KeyError):
        c.admit("no-such-class")
    c.release(t1)
    c.release(t2)
    assert c.in_flight == 0


def test_admission_grants_in_edf_order():
    c = _controller(concurrency=1)
    holder = c.admit("interactive")
    order = []
    now = time.monotonic()

    def waiter(name, deadline_s):
        t = c.admit("interactive", deadline=now + deadline_s)
        order.append(name)
        c.release(t)

    late = threading.Thread(target=waiter, args=("late", 30.0))
    early = threading.Thread(target=waiter, args=("early", 10.0))
    late.start()
    time.sleep(0.2)          # "late" queues first, but...
    early.start()
    time.sleep(0.2)
    c.release(holder)        # ...the grant goes to the EARLIER deadline
    late.join(timeout=30)
    early.join(timeout=30)
    assert order == ["early", "late"]


def test_admission_queue_full_sheds_latest_deadline():
    c = _controller(concurrency=1, queue_capacity=1)
    holder = c.admit("interactive")
    now = time.monotonic()
    shed = {}

    def waiter(name, deadline_s):
        try:
            t = c.admit("batch", deadline=now + deadline_s)
            c.release(t)
        except AdmissionShed as exc:
            shed[name] = exc

    far = threading.Thread(target=waiter, args=("far", 60.0))
    far.start()
    time.sleep(0.2)
    # queue full with "far": a later-deadline arrival is itself shed...
    with pytest.raises(AdmissionShed) as err:
        c.admit("batch", deadline=now + 120.0)
    assert err.value.reason == "queue_full"
    assert err.value.retry_after > 0
    # ...while an earlier-deadline arrival evicts "far" from the queue
    near = threading.Thread(target=waiter, args=("near", 10.0))
    near.start()
    far.join(timeout=30)
    assert shed["far"].reason == "queue_full"
    c.release(holder)
    near.join(timeout=30)
    assert "near" not in shed
    assert c.m_shed.value(**{"class": "batch", "reason": "queue_full"}) == 2


def test_admission_rate_limit_and_brownout_shed():
    c = _controller(concurrency=4,
                    policies=default_policies(rates={"batch": 1.0}))
    t = c.admit("batch")                     # burst of 1
    with pytest.raises(AdmissionShed) as err:
        c.admit("batch")
    assert err.value.reason == "rate"
    c.release(t)
    # brownout: listed classes shed at the door, others unaffected
    c.set_shed_classes({"best_effort"})
    with pytest.raises(AdmissionShed) as err:
        c.admit("best_effort")
    assert err.value.reason == "brownout"
    c.release(c.admit("interactive"))
    c.set_shed_classes(())
    c.release(c.admit("best_effort"))


def test_admission_expired_in_queue_sheds_not_grants():
    c = _controller(concurrency=1)
    holder = c.admit("interactive")
    # deadline passes while queued: the waiter withdraws and sheds
    with pytest.raises(AdmissionShed) as err:
        c.admit("interactive", deadline=time.monotonic() + 0.3)
    assert err.value.reason == "expired"
    # an ALREADY-expired deadline is refused without queueing
    with pytest.raises(AdmissionShed) as err:
        c.admit("interactive", deadline=time.monotonic() - 1.0)
    assert err.value.reason == "expired"
    assert c.queue_depth == 0
    c.release(holder)


def test_admission_release_sheds_expired_queue_heads():
    """A release pops expired waiters as sheds instead of granting work
    that would only 504 mid-flight."""
    c = _controller(concurrency=1)
    holder = c.admit("interactive")
    outcome = {}

    def waiter():
        try:
            outcome["t"] = c.admit("interactive",
                                   deadline=time.monotonic() + 5.0)
        except AdmissionShed as exc:
            outcome["shed"] = exc.reason

    w = threading.Thread(target=waiter)
    w.start()
    time.sleep(0.3)                  # let it queue (own timeout far off)
    # injectable now: the release's grant pass sees the deadline as
    # already lapsed and sheds instead of granting
    c.release(holder, now=time.monotonic() + 10.0)
    w.join(timeout=30)
    assert outcome.get("shed") == "expired"
    assert c.in_flight == 0 and c.queue_depth == 0


def test_admission_close_sheds_waiters_with_shutdown():
    c = _controller(concurrency=1)
    holder = c.admit("interactive")
    outcome = {}

    def waiter():
        try:
            c.admit("batch")
        except AdmissionShed as exc:
            outcome["reason"] = exc.reason

    w = threading.Thread(target=waiter)
    w.start()
    time.sleep(0.2)
    c.close()
    w.join(timeout=30)
    assert outcome["reason"] == "shutdown"
    with pytest.raises(AdmissionShed) as err:
        c.admit("interactive")
    assert err.value.reason == "shutdown"
    del holder


def test_admission_retry_after_tracks_service_rate():
    c = _controller(concurrency=1, retry_after_fallback=9.0)
    assert c.retry_after() == 9.0            # no completions yet: fallback
    now = time.monotonic()
    for i in range(5):                       # 10 completions/s observed
        c.release(c.admit("interactive"), now=now + i * 0.1)
    ra_idle = c.retry_after(backlog=0)
    ra_deep = c.retry_after(backlog=50)
    assert ra_idle < ra_deep                 # deeper backlog -> later retry
    assert ra_deep == pytest.approx(51 / 10.0, rel=0.5)


def test_admission_metrics_and_snapshot():
    reg = prom.Registry()
    c = _controller(concurrency=1, registry=reg)
    c.release(c.admit("interactive"))
    with pytest.raises(AdmissionShed):
        c.admit("interactive", deadline=time.monotonic() - 1.0)
    snap = c.snapshot()
    assert snap["in_flight"] == 0 and snap["queue_depth"] == 0
    assert snap["shed_total"] == 1 and snap["concurrency"] == 1
    text = reg.render()
    # the full (class, reason) matrix renders from the first scrape
    assert ('pipeedge_requests_shed_total{class="interactive",'
            'reason="expired"} 1') in text
    assert ('pipeedge_requests_shed_total{class="best_effort",'
            'reason="brownout"} 0') in text
    assert ('pipeedge_admission_latency_seconds_count'
            '{class="interactive"} 1') in text
    assert "pipeedge_admission_queue_depth 0" in text


# ---------------------------------------------------------------------------
# brownout ladder
# ---------------------------------------------------------------------------

def _ladder(**kw):
    kw.setdefault("registry", prom.Registry())
    kw.setdefault("marks", Watermarks(queue_high=4, queue_low=1,
                                      p95_high_s=1.0, p95_low_s=0.2,
                                      dwell_up_s=1.0, dwell_down_s=2.0))
    return BrownoutLadder(**kw)


def test_brownout_steps_up_one_rung_per_dwell():
    lad = _ladder()
    assert lad.update(10, None, now=0.0) == 0     # hot, but dwelling
    assert lad.update(10, None, now=0.5) == 0
    assert lad.update(10, None, now=1.0) == 1     # dwell_up_s elapsed
    assert lad.update(10, None, now=1.5) == 1     # re-armed: one per dwell
    assert lad.update(10, None, now=2.0) == 2
    assert lad.update(0, 5.0, now=3.0) == 3       # p95 alone is hot too
    assert lad.level_name == "evict_cold_pages"
    assert lad.update(10, None, now=4.0) == 4
    assert lad.level_name == "colocate_prefill"
    assert lad.update(10, None, now=5.0) == 5
    assert lad.update(10, None, now=6.0) == 6
    assert lad.update(10, None, now=9.0) == 6     # capped at max rung
    assert lad.level_name == "shed_batch"


def test_brownout_steps_down_with_hysteresis():
    lad = _ladder()
    lad.update(10, None, now=0.0)
    lad.update(10, None, now=1.0)
    lad.update(10, None, now=2.0)
    assert lad.level == 2
    # calm must persist dwell_down_s (2s) per rung
    assert lad.update(0, 0.1, now=2.5) == 2
    assert lad.update(0, 0.1, now=4.5) == 1
    assert lad.update(0, 0.1, now=5.0) == 1
    assert lad.update(0, 0.1, now=6.5) == 0
    # an idle window (no p95) counts as calm, not hot
    assert lad.update(0, None, now=7.0) == 0


def test_brownout_between_marks_holds_and_resets_dwells():
    lad = _ladder()
    lad.update(10, None, now=0.0)
    lad.update(10, None, now=1.0)
    assert lad.level == 1
    # queue between low and high: hold the rung, restart BOTH dwells
    assert lad.update(2, 0.5, now=1.5) == 1
    assert lad.update(10, None, now=2.0) == 1     # hot dwell restarted
    assert lad.update(10, None, now=3.0) == 2
    assert lad.update(0, 0.1, now=3.5) == 2
    assert lad.update(2, 0.5, now=4.0) == 2       # calm dwell restarted
    assert lad.update(0, 0.1, now=4.5) == 2
    assert lad.update(0, 0.1, now=6.5) == 1


def test_brownout_lifecycle_floor_and_effects():
    lad = _ladder()
    assert lad.allow_speculative()
    # healing implies at least rung 1, whatever the watermarks say
    assert lad.set_floor(1) == 1
    assert not lad.allow_speculative()
    assert lad.update(0, 0.1, now=100.0) == 1     # calm cannot go below
    assert lad.set_floor(0) == 0
    # effects ladder: clamp at >=2, evict cold KV pages at >=3,
    # colocate prefill at >=4, shed best_effort at >=5, batch at >=6
    assert lad.clamp(100) == 100
    lad.update(10, None, now=200.0)
    lad.update(10, None, now=201.0)
    lad.update(10, None, now=202.0)
    assert lad.level == 2 and lad.clamp(100) == lad.clamp_new_tokens
    assert lad.shed_classes() == frozenset()
    evictions = []
    lad.evict_hook = lambda: evictions.append(1) or 1
    lad.update(10, None, now=203.0)
    assert lad.level == 3 and lad.shed_classes() == frozenset()
    assert evictions, "evict_cold_pages rung never called its hook"
    assert lad.allow_disaggregate()
    lad.update(10, None, now=204.0)
    # colocate_prefill: shipping stops BEFORE any request class sheds
    assert lad.level == 4 and not lad.allow_disaggregate()
    assert lad.shed_classes() == frozenset()
    lad.update(10, None, now=205.0)
    assert lad.shed_classes() == frozenset({"best_effort"})
    lad.update(10, None, now=206.0)
    assert lad.shed_classes() == frozenset({"best_effort", "batch"})
    # the hook keeps firing while the ladder holds at/above the rung
    # (pages that re-chill during a long hot spell keep reclaiming)
    n = len(evictions)
    lad.update(10, None, now=206.5)
    assert len(evictions) > n
    snap = lad.snapshot()
    assert snap["level"] == 6 and snap["name"] == "shed_batch"
    assert snap["evicting"]


def test_brownout_gauge_and_transition_counter():
    reg = prom.Registry()
    lad = _ladder(registry=reg)
    lad.update(10, None, now=0.0)
    lad.update(10, None, now=1.0)
    assert "pipeedge_brownout_level 1" in reg.render()
    lad.update(0, 0.1, now=2.0)
    lad.update(0, 0.1, now=4.0)
    text = reg.render()
    assert "pipeedge_brownout_level 0" in text
    assert ('pipeedge_brownout_transitions_total{direction="up"} 1'
            in text)
    assert ('pipeedge_brownout_transitions_total{direction="down"} 1'
            in text)


# ---------------------------------------------------------------------------
# trace report: the serving section
# ---------------------------------------------------------------------------

def test_report_serving_section_from_spans():
    from pipeedge_tpu.telemetry import report

    ms = 1_000_000
    spans = [
        {"cat": "serve", "name": "admit:interactive", "rank": 0,
         "t0": 0, "t1": 2 * ms},
        {"cat": "serve", "name": "admit:interactive", "rank": 0,
         "t0": 0, "t1": 6 * ms},
        {"cat": "serve", "name": "generate", "rank": 0,
         "t0": 2 * ms, "t1": 50 * ms},
        {"cat": "serve", "name": "shed:batch:queue_full", "rank": 0,
         "t0": 10 * ms, "t1": 10 * ms},
        {"cat": "serve", "name": "shed:best_effort:brownout", "rank": 0,
         "t0": 11 * ms, "t1": 11 * ms},
        {"cat": "serve", "name": "shed:batch:rate", "rank": 0,
         "t0": 12 * ms, "t1": 12 * ms},
        {"cat": "serve", "name": "brownout:1", "rank": 0,
         "t0": 13 * ms, "t1": 13 * ms},
        {"cat": "serve", "name": "brownout:2", "rank": 0,
         "t0": 14 * ms, "t1": 14 * ms},
    ]
    serving = report.analyze_spans(spans, span_cost_ns=100.0)["serving"]
    assert serving["requests"] == 1
    assert serving["sheds"] == 3
    assert serving["sheds_by_class"] == {"batch": 2, "best_effort": 1}
    assert serving["sheds_by_reason"] == {"brownout": 1, "queue_full": 1,
                                          "rate": 1}
    w = serving["admit_wait_ms"]["interactive"]
    assert w["n"] == 2 and w["p50"] == 2.0 and w["p95"] == 6.0
    assert serving["brownout"] == {"transitions": 2, "max_level": 2}
    # traces without serve spans carry an empty section, not a crash
    other = [{"cat": "compute", "name": "x", "rank": 0, "t0": 0, "t1": ms}]
    assert report.analyze_spans(other, span_cost_ns=100.0)["serving"] == {}


# ---------------------------------------------------------------------------
# deadline propagation into the executor (the cancel-flag contract)
# ---------------------------------------------------------------------------

def _tiny_pipe(max_len=64):
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    total = registry.get_model_layers(MODEL)
    _, params, _ = registry.module_shard_factory(MODEL, None, 1, total,
                                                 unroll=False)
    return decode.DecodePipeline(
        registry.get_model_entry(MODEL).family.FAMILY,
        registry.get_model_config(MODEL), [(1, total)], [params],
        max_len=max_len)


def _result(batcher, drive, rid):
    """`rid`'s result from an executor driven the offline way (`run()`)
    or the served way (its own worker thread, `wait`)."""
    if drive == "thread":
        batcher.start()
    try:
        return (batcher.wait(rid, timeout=120) if drive == "thread"
                else batcher.run()[rid])
    finally:
        batcher.stop()


@pytest.mark.parametrize("drive", ["run", "thread"])
def test_pre_expired_deadline_never_touches_pipeline(drive):
    """A request whose deadline already passed completes with the bare
    prompt — no cache seeding, no decode steps spent on dead work."""
    import jax.numpy as jnp

    from pipeedge_tpu.parallel.batcher import ContinuousBatcher

    pipe = _tiny_pipe()
    ids = jnp.zeros((1, 4), jnp.int32)
    dead = time.monotonic() - 1.0
    b = ContinuousBatcher(pipe, max_active=1)
    b.submit("r", ids, 8, deadline=dead)
    out = _result(b, drive, "r")
    assert out.shape == (1, 4)               # prompt only, zero tokens
    assert b.stats["stage_steps"] == 0


@pytest.mark.parametrize("drive", ["run", "thread"])
def test_deadline_expiry_cancels_mid_flight(drive):
    """The executor checks the deadline at every decode-step boundary and
    fires the existing `cancel` flag on expiry: the request completes
    with the tokens decoded so far, far short of the cap — expired work
    stops consuming the pipeline (docs/SERVING.md)."""
    import jax.numpy as jnp

    from pipeedge_tpu.parallel.batcher import ContinuousBatcher

    pipe = _tiny_pipe()
    cap = 40
    ids = jnp.zeros((1, 4), jnp.int32)
    cancel = threading.Event()

    # pace decode via the streaming hook so the deadline trips after a
    # handful of steps regardless of host speed
    def on_token(step, tok):
        time.sleep(0.05)

    deadline = time.monotonic() + 0.3
    b = ContinuousBatcher(pipe, max_active=1)
    b.submit("r", ids, cap, on_token=on_token, cancel=cancel,
             deadline=deadline)
    out = _result(b, drive, "r")
    decoded = out.shape[1] - 4
    assert 1 <= decoded < cap, f"decoded {decoded} of {cap}"
    # expiry cancels through the ONE shared mechanism: the cancel flag
    assert cancel.is_set()


# ---------------------------------------------------------------------------
# the wired plane under fire (loopback serve.py + tools/loadgen.py)
# ---------------------------------------------------------------------------

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


def _healthz(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module")
def overload_server():
    """serve.py pinned to ONE execution slot with a tight brownout
    governor: capacity is small and deterministic, so '5x overload' is a
    modest absolute rate any test box can offer."""
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "-m", MODEL, "-pt", "1,4,5,8", "--max-len", "48",
         "-t", "float32", "--port", str(port),
         "--max-active", "1", "--queue-capacity", "16",
         "--brownout-queue-high", "4", "--brownout-queue-low", "1",
         "--brownout-p95-high", "0.75", "--brownout-p95-low", "0.3",
         "--brownout-dwell-up", "0.3", "--brownout-dwell-down", "0.7",
         "--brownout-clamp-tokens", "8", "--governor-interval", "0.1"],
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


class _LevelWatcher:
    """Polls /healthz brownout state in the background; records the max
    level seen and every (phase, level) pair while a degraded window was
    open."""

    def __init__(self, port, interval=0.1):
        self.port = port
        self.interval = interval
        self.max_level = 0
        self.degraded_samples = []
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.wait(self.interval):
            try:
                h = _healthz(self.port)
            except Exception:   # noqa: BLE001 — transient poll failure
                continue
            level = h["serving"]["brownout"]["level"]
            self.max_level = max(self.max_level, level)
            if h["degraded"]:
                self.degraded_samples.append(
                    (h["degraded"]["phase"], level))

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join(timeout=10)


@pytest.mark.fleet
def test_overload_5x_bounded_goodput_shed_and_brownout(overload_server):
    """The acceptance run (ISSUE 7): at 5x sustained synthetic overload,
    interactive goodput stays within 20% of its uncontended value, the
    excess converts to 503 + a DYNAMIC Retry-After (never an unbounded
    queue), and the brownout ladder steps up under fire and back down
    with hysteresis once the surge passes."""
    from tools import loadgen

    port = overload_server
    url = f"http://127.0.0.1:{port}/generate"
    slo = {"interactive": 2000.0, "batch": 6000.0, "best_effort": 10000.0}
    capacity = loadgen.calibrate(url, seconds=2.0, new_tokens=40,
                                 prompt_len=6, timeout=120)
    assert capacity > 0

    # uncontended baseline: interactive only, well under capacity
    base = loadgen.run_load(url, duration_s=4.0, qps=max(0.5, 0.6 * capacity),
                            mix={"interactive": 1.0}, slo_ms=slo,
                            new_tokens=40, timeout=120, seed=1)
    assert base["totals"]["error"] == 0, base["first_error"]
    base_goodput = base["classes"]["interactive"]["goodput_rps"]
    assert base_goodput > 0

    # 5x overload, mixed classes, brownout watched live
    with _LevelWatcher(port) as watch:
        hot = loadgen.run_load(
            url, duration_s=6.0, qps=5.0 * capacity,
            mix={"interactive": 0.6, "batch": 0.25, "best_effort": 0.15},
            slo_ms=slo, new_tokens=40, timeout=120, seed=2)
    assert hot["totals"]["error"] == 0, hot["first_error"]
    # the offered load genuinely overloaded the service (>= 3x even if
    # the client box lagged behind the 5x schedule)
    offered = hot["requests"] / hot["duration_s"]
    assert offered >= 3.0 * capacity, (offered, capacity)
    # excess load SHED, with a service-rate-derived (dynamic) Retry-After
    assert hot["totals"]["shed"] > 0
    ra = hot["retry_after"]
    assert ra["n"] > 0 and ra["min"] > 0
    assert ra["distinct"] >= 2, f"Retry-After looks constant: {ra}"
    # interactive goodput held within 20% of the uncontended value
    hot_goodput = hot["classes"]["interactive"]["goodput_rps"]
    assert hot_goodput >= 0.8 * base_goodput, (hot_goodput, base_goodput)
    # the ladder stepped up under fire...
    assert watch.max_level >= 1, "brownout never engaged at 5x overload"
    # ...and steps back down (hysteresis: dwell_down per rung) once calm
    deadline = time.monotonic() + 20
    while _healthz(port)["serving"]["brownout"]["level"] > 0:
        assert time.monotonic() < deadline, \
            "brownout ladder never stepped back down after the surge"
        time.sleep(0.2)
    # nothing queued unbounded, nothing stuck
    h = _healthz(port)
    assert h["ok"]
    adm = h["serving"]["admission"]
    assert adm["queue_depth"] <= adm["queue_capacity"]


@pytest.mark.fleet
def test_deadline_exceeded_504_mid_flight(overload_server):
    """A request whose budget cannot cover its generation is cancelled at
    a decode-step boundary and answered 504 — with strictly fewer tokens
    decoded than the cap (the executor cancel flag did the work)."""
    port = overload_server
    before = _healthz(port)
    tokens_before = before["stats"]["tokens"]
    d_before = before["serving"]["deadline_exceeded_total"]
    cap = 40
    with pytest.raises(urllib.error.HTTPError) as err:
        _post(port, "/generate", {"ids": [[1, 2, 3, 4, 5, 6]],
                                  "new_tokens": cap, "deadline_ms": 10})
    assert err.value.code == 504
    body = json.loads(err.value.read())
    assert body["deadline_exceeded"] and body["class"] == "interactive"
    after = _healthz(port)
    assert after["serving"]["deadline_exceeded_total"] == d_before + 1
    assert after["stats"]["tokens"] - tokens_before < cap
    # the slot freed: a normal request sails through afterwards
    out = _post(port, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2})
    assert len(out["ids"][0]) == 5


@pytest.mark.fleet
def test_overload_overlapping_rank_death_no_deadlock(overload_server):
    """Overload overlapping a chaos-injected rank death (the /degraded
    lifecycle the failover orchestrator drives at a kill@K fault): the
    load generator must complete — degraded 503s, not hangs — the
    healing phase floors the brownout ladder at rung 1, and the service
    returns to normal once healed."""
    from tools import loadgen

    port = overload_server
    url = f"http://127.0.0.1:{port}/generate"

    def chaos():
        time.sleep(1.2)
        _post(port, "/degraded", {"degraded": True, "dead_rank": 1})
        time.sleep(1.0)
        _post(port, "/degraded", {"degraded": True, "healing": True})
        time.sleep(1.0)
        _post(port, "/degraded", {"degraded": False, "healed": True,
                                  "rank": 1})

    killer = threading.Thread(target=chaos, daemon=True)
    with _LevelWatcher(port) as watch:
        killer.start()
        report = loadgen.run_load(
            url, duration_s=5.0, qps=30.0,
            mix={"interactive": 0.7, "batch": 0.3},
            slo_ms={"interactive": 2000.0, "batch": 6000.0},
            new_tokens=24, timeout=120, seed=3)
        killer.join(timeout=30)
        assert not killer.is_alive()
    # the window bounced load with degraded 503s instead of queueing it
    assert report["totals"]["degraded"] > 0
    assert report["totals"]["error"] == 0, report["first_error"]
    # healing implies at least brownout rung 1 (the lifecycle floor)
    healing = [lvl for phase, lvl in watch.degraded_samples
               if phase == "healing"]
    assert healing and min(healing) >= 1, watch.degraded_samples
    # no deadlock: the service is clean and serving after the heal
    deadline = time.monotonic() + 20
    while True:
        h = _healthz(port)
        if h["degraded"] is False and h["serving"]["brownout"]["level"] == 0:
            break
        assert time.monotonic() < deadline, h
        time.sleep(0.2)
    out = _post(port, "/generate", {"ids": [[1, 2, 3]], "new_tokens": 2})
    assert len(out["ids"][0]) == 5
    assert h["stats"]["rejoined_ranks_total"] >= 1


@pytest.mark.fleet
def test_overload_metrics_exported(overload_server):
    """After the fire drill, every new instrument is live on /metrics:
    per-(class, reason) shed counters, the brownout level gauge, the
    mid-flight deadline counter, and the per-class admission-latency
    histogram (docs/OBSERVABILITY.md table)."""
    port = overload_server
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        text = resp.read().decode()
    assert "# TYPE pipeedge_requests_shed_total counter" in text
    from pipeedge_tpu.serving.admission import SHED_REASONS
    shed_lines = [ln for ln in text.splitlines()
                  if ln.startswith("pipeedge_requests_shed_total{")]
    # the full (class, reason) matrix renders, and something was shed
    assert len(shed_lines) == 3 * len(SHED_REASONS), shed_lines
    assert any(float(ln.rsplit(" ", 1)[1]) > 0 for ln in shed_lines)
    assert "pipeedge_brownout_level" in text
    assert "pipeedge_brownout_transitions_total" in text
    assert "pipeedge_deadline_exceeded_total" in text
    assert ('pipeedge_admission_latency_seconds_bucket{class="interactive"'
            in text)
    assert "pipeedge_admission_queue_depth" in text
