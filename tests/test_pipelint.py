"""pipelint + lockdep tests (docs/STATIC_ANALYSIS.md).

One violating + one clean fixture per AST rule (the violating snippet
proves the rule FIRES, the clean one bounds its false positives),
suppression and baseline behavior, the CLI's exit-code contract, the
dcn protocol-table import self-check, and the runtime lock-order witness
(a real A->B / B->A cycle across two threads, condition-wait exemption,
blocking-under-lock detection).
"""
import json
import subprocess
import sys
import threading
import time

import pytest

from pipeedge_tpu.analysis import lint, lockdep


def run_on(tmp_path, source, name="snippet.py"):
    """Lint one source snippet; returns the list of fired rule ids."""
    p = tmp_path / name
    p.write_text(source)
    findings, errors, n = lint.run_lint([str(p)])
    assert not errors, errors
    assert n == 1
    return findings


def rule_ids(findings):
    return sorted({f.rule for f in findings})


# -- PL101 lock-guarded-field-write --------------------------------------

PL101_BAD = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0

    def bump(self):
        with self._lock:
            self._count += 1

    def reset(self):
        self._count = 0
"""

PL101_CLEAN = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0

    def bump(self):
        with self._lock:
            self._count += 1

    def reset(self):
        with self._lock:
            self._count = 0

    def _drain_locked(self):
        self._count = 0    # _locked suffix: caller holds the lock
"""


def test_pl101_fires(tmp_path):
    findings = run_on(tmp_path, PL101_BAD)
    assert "PL101" in rule_ids(findings)
    (f,) = [f for f in findings if f.rule == "PL101"]
    assert "_count" in f.message and f.symbol == "C.reset"


def test_pl101_clean(tmp_path):
    assert "PL101" not in rule_ids(run_on(tmp_path, PL101_CLEAN))


# -- PL102 blocking-call-under-lock --------------------------------------

PL102_BAD = """
import time

class C:
    def flush(self, sock, payload):
        with self._lock:
            sock.sendall(payload)
            time.sleep(0.1)
"""

PL102_CLEAN = """
class C:
    def flush(self, sock, payload):
        with self._lock:
            data = dict(self._pending)     # snapshot under the lock
            meta = data.get("k", None)     # dict.get: not a queue wait
        sock.sendall(data)

    def wait_ready(self):
        with self._cond:
            self._cond.wait_for(lambda: self._ready)   # releases the lock

    def render(self, parts):
        with self._lock:
            return ", ".join(parts)        # str.join: not a thread join
"""


def test_pl102_fires(tmp_path):
    findings = [f for f in run_on(tmp_path, PL102_BAD) if f.rule == "PL102"]
    assert len(findings) == 2    # sendall + sleep
    assert any("sendall" in f.message for f in findings)
    assert any("sleep" in f.message for f in findings)


def test_pl102_clean(tmp_path):
    assert "PL102" not in rule_ids(run_on(tmp_path, PL102_CLEAN))


# -- PL201 thread-without-join-or-daemon ---------------------------------

PL201_BAD = """
import threading

def spawn():
    t = threading.Thread(target=work)
    t.start()
"""

PL201_CLEAN = """
import threading

class C:
    def start(self):
        self._bg = threading.Thread(target=work, daemon=True)
        self._bg.start()
        self._pump = threading.Thread(target=pump)
        self._pump.start()

    def close(self):
        self._pump.join()
"""


def test_pl201_fires(tmp_path):
    findings = run_on(tmp_path, PL201_BAD)
    assert "PL201" in rule_ids(findings)


def test_pl201_clean(tmp_path):
    assert "PL201" not in rule_ids(run_on(tmp_path, PL201_CLEAN))


def test_pl201_explicit_daemon_false_still_needs_join(tmp_path):
    # daemon=False is a CHOICE of a non-daemon thread, not an exemption
    src = """
import threading

def spawn():
    t = threading.Thread(target=work, daemon=False)
    t.start()
"""
    assert "PL201" in rule_ids(run_on(tmp_path, src))


def test_pl201_computed_daemon_value_is_owned(tmp_path):
    src = """
import threading

def spawn(flag):
    t = threading.Thread(target=work, daemon=flag)
    t.start()
"""
    assert "PL201" not in rule_ids(run_on(tmp_path, src))


def test_pl201_join_via_loop_variable(tmp_path):
    src = """
import threading

class C:
    def start(self):
        self._workers = [threading.Thread(target=work) for _ in range(4)]

    def stop(self):
        for w in self._workers:
            w.join()
"""
    assert "PL201" not in rule_ids(run_on(tmp_path, src))


# -- PL301 jit-in-loop ---------------------------------------------------

PL301_BAD = """
import jax

def run(microbatches):
    for mb in microbatches:
        fn = jax.jit(step)
        fn(mb)
"""

PL301_CLEAN = """
import jax

fn = jax.jit(step)

def run(microbatches):
    for mb in microbatches:
        fn(mb)

def make(variant):
    # a jit inside a nested def that the loop merely DEFINES is deferred
    for v in (1, 2):
        def build():
            return jax.jit(step)
"""


def test_pl301_fires(tmp_path):
    assert "PL301" in rule_ids(run_on(tmp_path, PL301_BAD))


def test_pl301_clean(tmp_path):
    assert "PL301" not in rule_ids(run_on(tmp_path, PL301_CLEAN))


# -- PL302 donated-arg-reuse ---------------------------------------------

PL302_BAD = """
import jax

fn = jax.jit(step, donate_argnums=(0,))

def run(payload):
    out = fn(payload)
    return payload.sum()
"""

PL302_CLEAN = """
import jax

fn = jax.jit(step, donate_argnums=(0,))
plain = jax.jit(step)

def run(payload):
    out = fn(payload)
    return out.sum()

def rebind(payload):
    payload = fn(payload)      # x = fn(x): the later read is the result
    return payload.sum()

def undonated(payload):
    out = plain(payload)
    return payload.sum()
"""


def test_pl302_fires(tmp_path):
    findings = run_on(tmp_path, PL302_BAD)
    assert "PL302" in rule_ids(findings)


def test_pl302_clean(tmp_path):
    assert "PL302" not in rule_ids(run_on(tmp_path, PL302_CLEAN))


# -- PL303 host-sync-in-dispatch-path ------------------------------------

PL303_BAD = """
import numpy as np

def dispatch_microbatch(out):
    host = np.asarray(out)      # D2H sync in the hot dispatch path
    return host
"""

PL303_CLEAN = """
import numpy as np

def dispatch_microbatch(out):
    return out                  # stays async

def readback(out):
    return np.asarray(out)      # syncs belong on the readback side
"""


def test_pl303_fires(tmp_path):
    assert "PL303" in rule_ids(run_on(tmp_path, PL303_BAD))


def test_pl303_clean(tmp_path):
    assert "PL303" not in rule_ids(run_on(tmp_path, PL303_CLEAN))


# -- PL401/PL402 protocol table ------------------------------------------

PL401_BAD = """
_MSG_A = 1
_MSG_B = 1

def dispatch(t):
    if t == _MSG_A:
        pass
    elif t == _MSG_B:
        pass
"""

PL402_BAD = """
_MSG_A = 1
_MSG_ORPHAN = 2

def dispatch(t):
    if t == _MSG_A:
        pass
"""

PL40X_CLEAN = """
_MSG_A = 1
_MSG_B = 2

def dispatch(t):
    if t == _MSG_A:
        pass
    elif t == _MSG_B:
        pass
"""


def test_pl401_fires(tmp_path):
    findings = run_on(tmp_path, PL401_BAD)
    assert "PL401" in rule_ids(findings)


def test_pl402_fires(tmp_path):
    findings = run_on(tmp_path, PL402_BAD)
    assert "PL402" in rule_ids(findings)
    (f,) = [f for f in findings if f.rule == "PL402"]
    assert "_MSG_ORPHAN" in f.message


def test_pl40x_clean(tmp_path):
    ids = rule_ids(run_on(tmp_path, PL40X_CLEAN))
    assert "PL401" not in ids and "PL402" not in ids


# -- PL403 missing-retry-after -------------------------------------------

PL403_BAD = """
class Handler:
    def reject(self):
        self.send_response(503)
        self.end_headers()
"""

PL403_CLEAN = """
class Handler:
    def reject(self):
        self.send_response(503)
        self.send_header("Retry-After", "5")
        self.end_headers()

    def shed(self, hint):
        self._send(503, {"error": "shed"},
                   extra_headers={"Retry-After": f"{hint:g}"})
"""


def test_pl403_fires(tmp_path):
    assert "PL403" in rule_ids(run_on(tmp_path, PL403_BAD))


def test_pl403_clean(tmp_path):
    assert "PL403" not in rule_ids(run_on(tmp_path, PL403_CLEAN))


def test_pl403_compliant_path_does_not_immunize_siblings(tmp_path):
    # one 503-with-Retry-After in a function must not silence a second,
    # bare 503 path beside it
    src = """
class Handler:
    def handle(self, shed):
        if shed:
            self.send_response(503)
            self.send_header("Retry-After", "5")
            self.end_headers()
            return
        do_other_work()
        check_more_state()
        and_some_more()
        if self.dead:
            self.send_response(503)
            self.end_headers()
"""
    findings = run_on(tmp_path, src)
    assert [f.rule for f in findings] == ["PL403"]
    assert findings[0].line > 10    # fired on the SECOND path only


# -- PL501 undeclared-metric-labels --------------------------------------

PL501_BAD = """
from pipeedge_tpu.telemetry import metrics as prom

_EVENTS = prom.REGISTRY.counter("events_total", "events by kind")

def record(kind):
    _EVENTS.inc(kind=kind)
"""

PL501_CLEAN = """
from pipeedge_tpu.telemetry import metrics as prom

_EVENTS = prom.REGISTRY.counter("events_total", "events by kind")
for kind in ("a", "b"):
    _EVENTS.declare(kind=kind)

_TOTAL = prom.REGISTRY.counter("plain_total", "unlabeled")

def record(kind):
    _EVENTS.inc(kind=kind)
    _TOTAL.inc()
"""


def test_pl501_fires(tmp_path):
    findings = run_on(tmp_path, PL501_BAD)
    assert "PL501" in rule_ids(findings)
    (f,) = [f for f in findings if f.rule == "PL501"]
    assert "events_total" in f.message


def test_pl501_clean(tmp_path):
    assert "PL501" not in rule_ids(run_on(tmp_path, PL501_CLEAN))


def test_pl501_declare_in_other_file(tmp_path):
    """The declare may live in a different module than the inc (the
    cross-file collect pass)."""
    (tmp_path / "metrics_def.py").write_text(PL501_BAD)
    (tmp_path / "declares.py").write_text("""
from metrics_def import _EVENTS
_EVENTS.declare(kind="a")
""")
    findings, errors, n = lint.run_lint([str(tmp_path)])
    assert not errors and n == 2
    assert "PL501" not in rule_ids(findings)


# -- PL502 unpaired-span -------------------------------------------------

PL502_BAD = """
from pipeedge_tpu import telemetry

def measure():
    s = telemetry.span("stage", "dispatch")
    s.__enter__()
"""

PL502_CLEAN = """
from pipeedge_tpu import telemetry

def measure():
    with telemetry.span("stage", "dispatch"):
        pass

def probe(rec):
    return rec.span("stage", "dispatch")   # factory return: the API itself
"""


PL502_REQUEST_BAD = """
from pipeedge_tpu import telemetry

def run_stage(req, i):
    # request-tagged span created outside `with`: the rid tag does not
    # exempt it — an error path still leaks the begin stamp
    s = telemetry.span("stage", f"exec{i}", stage=i, rid=str(req.rid))
    s.__enter__()
"""

PL502_REQUEST_CLEAN = """
from pipeedge_tpu import telemetry

def run_stage(req, i, trace):
    rid = trace.rid if trace is not None else None
    with telemetry.span("stage", "dispatch", stage=i, mb=0, rid=rid):
        pass
    # cross-thread request pairs belong to record(), which is not a span
    telemetry.record("serve", "admit:interactive", 0, 1, rid=rid)
"""


PL502_STARTUP_BAD = """
from pipeedge_tpu import telemetry

def load():
    phase = telemetry.startup("weights_read")    # the same probe, a counter
    phase.__enter__()
"""


@pytest.mark.parametrize("source", [PL502_BAD, PL502_STARTUP_BAD],
                         ids=["span", "startup"])
def test_pl502_fires(tmp_path, source):
    assert "PL502" in rule_ids(run_on(tmp_path, source))


def test_pl502_fires_on_request_tagged_span(tmp_path):
    assert "PL502" in rule_ids(run_on(tmp_path, PL502_REQUEST_BAD))


def test_pl502_clean(tmp_path):
    assert "PL502" not in rule_ids(run_on(tmp_path, PL502_CLEAN))


def test_pl502_clean_request_spans(tmp_path):
    assert "PL502" not in rule_ids(run_on(tmp_path, PL502_REQUEST_CLEAN))


# -- suppression + baseline ----------------------------------------------

def test_line_suppression(tmp_path):
    src = PL301_BAD.replace("fn = jax.jit(step)",
                            "fn = jax.jit(step)  # pipelint: disable=PL301")
    assert "PL301" not in rule_ids(run_on(tmp_path, src))


def test_line_suppression_is_rule_specific(tmp_path):
    src = PL301_BAD.replace("fn = jax.jit(step)",
                            "fn = jax.jit(step)  # pipelint: disable=PL999")
    assert "PL301" in rule_ids(run_on(tmp_path, src))


def test_file_suppression(tmp_path):
    src = "# pipelint: disable-file=PL301\n" + PL301_BAD
    assert "PL301" not in rule_ids(run_on(tmp_path, src))


def test_baseline_split_and_fingerprint_stability(tmp_path):
    findings = run_on(tmp_path, PL301_BAD)
    doc = json.loads(lint.Baseline.render(
        findings, {f.fingerprint: "grandfathered" for f in findings}))
    baseline = lint.Baseline(doc["findings"])
    # same code shifted to different lines: fingerprints still match
    shifted = run_on(tmp_path, "\n\n\n" + PL301_BAD, name="shifted.py")
    # (path differs -> fingerprint differs; use the same file instead)
    same = run_on(tmp_path, "# a comment\n" + PL301_BAD)
    new, baselined, stale = baseline.split(same)
    assert not new and baselined and not stale
    assert shifted[0].fingerprint != findings[0].fingerprint  # path-bound


def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"findings": [
        {"fingerprint": "abc123", "rule": "PL301", "path": "x.py",
         "justification": "   "}]}))
    with pytest.raises(lint.LintError, match="justification"):
        lint.Baseline.load(str(p))


# -- CLI -----------------------------------------------------------------

def _cli(args, cwd):
    return subprocess.run(
        [sys.executable, "-m", "tools.pipelint", *args],
        cwd=cwd, capture_output=True, text=True)


@pytest.mark.slow
def test_cli_exit_codes(tmp_path):
    import os
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = tmp_path / "bad.py"
    bad.write_text(PL301_BAD)
    clean = tmp_path / "clean.py"
    clean.write_text(PL301_CLEAN)
    r = _cli([str(clean), "--no-baseline"], repo_root)
    assert r.returncode == 0, r.stdout + r.stderr
    r = _cli([str(bad), "--no-baseline", "--json", "-"], repo_root)
    assert r.returncode == 1
    report = json.loads(r.stdout.splitlines()[0])
    assert report["counts_by_rule"].get("PL301") == 1
    assert not report["ok"]


@pytest.mark.slow
def test_cli_repo_tree_is_clean():
    """The acceptance gate: the shipped tree lints clean against the
    shipped (justified) baseline."""
    import os
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = _cli(["pipeedge_tpu", "tools", "runtime.py"], repo_root)
    assert r.returncode == 0, r.stdout + r.stderr


def test_rule_catalog_has_ten_distinct_rules():
    rules = lint.default_rules()
    ids = [r.id for r in rules]
    assert len(ids) == len(set(ids))
    assert len(ids) >= 10
    for r in rules:
        assert r.rationale and r.fix_hint and r.severity in (
            lint.SEVERITY_ERROR, lint.SEVERITY_WARNING)


# -- dcn protocol-table self-check ---------------------------------------

def test_dcn_protocol_self_check_passes():
    from pipeedge_tpu.comm import dcn
    dcn._check_protocol_table()    # the import already ran it; idempotent


def test_dcn_protocol_self_check_catches_collision(monkeypatch):
    from pipeedge_tpu.comm import dcn
    monkeypatch.setattr(dcn, "_MSG_FAKE_DUPE", dcn._MSG_TENSORS,
                        raising=False)
    with pytest.raises(AssertionError, match="collision"):
        dcn._check_protocol_table()


def test_dcn_protocol_self_check_catches_orphan(monkeypatch):
    from pipeedge_tpu.comm import dcn
    monkeypatch.setattr(dcn, "_MSG_FAKE_ORPHAN", 99, raising=False)
    with pytest.raises(AssertionError, match="no _reader_loop dispatch"):
        dcn._check_protocol_table()


# -- lockdep runtime witness ---------------------------------------------

def test_lockdep_witnesses_ab_ba_cycle():
    """Two threads taking the same pair of locks in opposite orders: the
    witness convicts the inversion WITHOUT needing the actual deadlock
    interleaving (the threads run sequentially here)."""
    st = lockdep.LockdepState()
    a = lockdep.TrackedLock(st, "A")
    b = lockdep.TrackedLock(st, "B")

    def fwd():
        with a:
            with b:
                pass

    def rev():
        with b:
            with a:
                pass

    for target in (fwd, rev):
        t = threading.Thread(target=target)
        t.start()
        t.join()
    cycles = st.cycles()
    assert cycles == [["A", "B"]]
    witnesses = st.edge_witnesses(cycles[0])
    held = {(w["held"], w["acquired"]) for w in witnesses}
    assert held == {("A", "B"), ("B", "A")}
    rep = st.report()
    assert rep["cycles"] == [["A", "B"]] and rep["threads"] == 2


def test_lockdep_duplicate_fingerprints_are_occurrence_indexed(tmp_path):
    # two identical violations in one function: distinct fingerprints, so
    # a baseline entry for the first never grandfathers the second
    src = """
import threading

class C:
    def send_twice(self):
        with self._lock:
            self._sock.sendall(b"a")
            self._sock.sendall(b"a")
"""
    findings = [f for f in run_on(tmp_path, src) if f.rule == "PL102"]
    assert len(findings) == 2
    fps = [f.fingerprint for f in findings]
    assert len(set(fps)) == 2
    assert fps[1] == fps[0] + "#2"
    bl = lint.Baseline([{"fingerprint": fps[0], "justification": "first"}])
    new, baselined, _ = bl.split(findings)
    assert len(baselined) == 1 and len(new) == 1
    assert new[0].fingerprint == fps[1]


def test_lockdep_two_instances_of_one_name_self_edge():
    """Nesting two INSTANCES of one lock site is the rank-N deadlock
    shape (thread 1: a->b, thread 2: b->a, same site): the name-folded
    graph records a self-edge and convicts it as a cycle."""
    st = lockdep.LockdepState()
    a = lockdep.TrackedLock(st, "pool")
    b = lockdep.TrackedLock(st, "pool")
    with a:
        with b:
            pass
    assert st.cycles() == [["pool"]]


def test_lockdep_reentrant_same_instance_is_not_a_cycle():
    st = lockdep.LockdepState()
    r = lockdep.TrackedRLock(st, "reent")
    with r:
        with r:
            pass
    assert st.cycles() == []


def test_lockdep_consistent_order_is_clean():
    st = lockdep.LockdepState()
    a = lockdep.TrackedLock(st, "A")
    b = lockdep.TrackedLock(st, "B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert st.cycles() == []


def test_lockdep_blocking_under_lock_detected():
    prev = lockdep.state()
    st = lockdep.enable(lockdep.LockdepState())
    try:
        lk = lockdep.TrackedLock(st, "L")
        time.sleep(0.001)          # no lock held: clean
        with lk:
            time.sleep(0.001)      # held: violation
        rep = st.report()
        assert len(rep["blocking_violations"]) == 1
        v = rep["blocking_violations"][0]
        assert v["held"] == ["L"] and "sleep" in v["call"]
    finally:
        if prev is not None:
            lockdep.enable(prev)
        else:
            lockdep.disable()


def test_lockdep_condition_wait_releases_held_stack():
    """Condition.wait parks the thread but RELEASES the lock: the witness
    must not call that a blocking-under-lock violation."""
    prev = lockdep.state()
    st = lockdep.enable(lockdep.LockdepState())
    try:
        cond = threading.Condition(lockdep.TrackedRLock(st, "C"))
        done = []

        def waiter():
            with cond:
                cond.wait(timeout=5)
                done.append(True)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cond:
            assert st.held() == ("C",)
            cond.notify_all()
        t.join(timeout=5)
        assert done == [True]
        assert st.held() == ()
        # the waiter's park must not be recorded as held-across-blocking
        rep = st.report()
        assert all(v["held"] != ["C"] or "sleep" in v["call"]
                   for v in rep["blocking_violations"])
        assert rep["cycles"] == []
    finally:
        if prev is not None:
            lockdep.enable(prev)
        else:
            lockdep.disable()


def test_lockdep_dump_appends_json_lines(tmp_path):
    st = lockdep.LockdepState()
    with lockdep.TrackedLock(st, "X"):
        pass
    out = tmp_path / "lockdep.json"
    st.dump(str(out))
    st.dump(str(out))
    lines = out.read_text().splitlines()
    assert len(lines) == 2
    rep = json.loads(lines[0])
    assert rep["locks"] == ["X"] and rep["cycles"] == []


def test_make_lock_factories_track_when_enabled():
    from pipeedge_tpu.utils import threads
    prev = lockdep.state()
    st = lockdep.enable(lockdep.LockdepState())
    try:
        lk = threads.make_lock("t.lock")
        assert isinstance(lk, lockdep.TrackedLock)
        cond = threads.make_condition("t.cond")
        with cond:
            pass
        with lk:
            pass
        assert "t.lock" in st.report()["locks"]
        assert "t.cond" in st.report()["locks"]
    finally:
        if prev is not None:
            lockdep.enable(prev)
        else:
            lockdep.disable()
    if prev is None:
        # witness off again: the factory hands back a plain stdlib lock
        assert isinstance(threads.make_lock("plain"),
                          type(threading.Lock()))
