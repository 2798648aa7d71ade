"""The cross-process prefill fleet (pipeedge_tpu/kv/fleet.py): the
lease/ack ship protocol and its fault matrix.

The disaggregated split is only trustworthy if the ship edge survives
every fault deterministically (ISSUE 15): CRC-corrupt frame -> bounded
re-ship -> success; resend exhaustion -> colocated fallback with token
parity; worker death mid-lease -> re-dispatch to a survivor; zombie
acks (stale lease attempt) fenced; and the chaos acceptance — a prefill
worker PROCESS killed mid-burst with every in-flight request completing
token-identically and zero leaked pages.
"""
import os
import queue as queue_mod
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp  # noqa: E402

from pipeedge_tpu.comm import dcn  # noqa: E402
from pipeedge_tpu.kv import (PagedKvBackend, PrefillUnavailable,  # noqa: E402
                             PrefillWorkerLoop, RemotePrefillFleet)
from pipeedge_tpu.kv import fleet as fleet_mod  # noqa: E402
from pipeedge_tpu.parallel.batcher import ContinuousBatcher  # noqa: E402
from pipeedge_tpu.telemetry import metrics as prom  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "pipeedge/test-tiny-gpt2"
PARTITION = [(1, 4), (5, 8)]
MAX_LEN = 48


def _mk_pipe(max_len=MAX_LEN):
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    params = [registry.module_shard_factory(MODEL, None, l, r, stage=i,
                                            unroll=False)[1]
              for i, (l, r) in enumerate(PARTITION)]
    return decode.DecodePipeline(
        registry.get_model_entry(MODEL).family.FAMILY,
        registry.get_model_config(MODEL), PARTITION, params,
        max_len=max_len)


@pytest.fixture(scope="module")
def pipe():
    return _mk_pipe()


@pytest.fixture(scope="module")
def prefill_pipe():
    return _mk_pipe()


def _free_ports(n):
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _make_contexts(n):
    """Contexts with the LOCAL hand-off tier disabled: production
    prefill workers are separate processes, so the in-process test
    plane must ride the socket path too (the corrupt@K hook and the
    CRC layer live there)."""
    addrs = [("127.0.0.1", p) for p in _free_ports(n)]
    before = os.environ.get("DCN_LOCAL_HANDOFF")
    os.environ["DCN_LOCAL_HANDOFF"] = "0"
    try:
        ctxs = [dcn.DistDcnContext(n, r, addrs) for r in range(n)]
    finally:
        if before is None:
            os.environ.pop("DCN_LOCAL_HANDOFF", None)
        else:
            os.environ["DCN_LOCAL_HANDOFF"] = before
    for c in ctxs:
        c.init()
    return ctxs


def _backend(pipe, n_pages=24, page_size=4):
    return PagedKvBackend(pipe, n_pages, page_size,
                          registry=prom.Registry())


class _ShipPlane:
    """One decode rank + N in-process worker ranks over real sockets:
    the unit-test stand-in for the subprocess fleet (same frames, same
    transport, millisecond setup)."""

    def __init__(self, prefill_pipe, n_workers=1, start=True, **fleet_kw):
        self.ctxs = _make_contexts(1 + n_workers)
        self.workers = [PrefillWorkerLoop(prefill_pipe, self.ctxs[r])
                        for r in range(1, 1 + n_workers)]
        self.threads = []
        if start:
            for i, w in enumerate(self.workers):
                t = threading.Thread(target=w.run, daemon=True,
                                     name=f"test-prefill-w{i}")
                t.start()
                self.threads.append(t)
        fleet_kw.setdefault("registry", prom.Registry())
        fleet_kw.setdefault("lease_timeout_s", 30.0)
        self.fleet = RemotePrefillFleet(
            self.ctxs[0], ranks=range(1, 1 + n_workers),
            dtype=prefill_pipe.dtype, **fleet_kw)

    def close(self):
        self.fleet.close()
        for w in self.workers:
            w.stop()
        for t in self.threads:
            t.join(timeout=10)
        for c in self.ctxs:
            c.shutdown()


# ---------------------------------------------------------------------------
# protocol codec
# ---------------------------------------------------------------------------

def test_lease_and_ack_header_roundtrip():
    hdr = fleet_mod.lease_header(7, 2, 8, True, 1500.0)
    lease = fleet_mod.parse_lease_header(hdr)
    assert lease == {"lease_id": 7, "attempt": 2, "ship_bits": 8,
                     "crc": True, "deadline_ms": 1500}
    ack = fleet_mod.parse_ack_header(fleet_mod.ack_header(7, 2, 0))
    assert ack == {"lease_id": 7, "attempt": 2, "status": 0}
    with pytest.raises(ValueError, match="magic"):
        fleet_mod.parse_lease_header(np.asarray([1, 2, 3], np.int64))
    with pytest.raises(ValueError, match="magic"):
        fleet_mod.parse_ack_header(hdr)     # a lease is not an ack


# ---------------------------------------------------------------------------
# happy path: cross-context ship, token parity
# ---------------------------------------------------------------------------

def test_remote_prefill_token_parity(pipe, prefill_pipe):
    """Leases over real sockets produce token streams identical to solo
    dense generate(), greedy and sampled, on pinned seeds — the same
    gate the in-process fleet passes (test_kv_plane.py)."""
    plane = _ShipPlane(prefill_pipe)
    try:
        rng = np.random.default_rng(41)
        ids = rng.integers(0, 100, size=(1, 7))
        kv = _backend(pipe)
        batcher = ContinuousBatcher(pipe, kv=kv)
        batcher.submit("greedy", ids, new_tokens=6,
                       shipped=plane.fleet.prefill(ids, rid="greedy"))
        batcher.submit("sampled", ids, new_tokens=5, temperature=0.9,
                       seed=6,
                       shipped=plane.fleet.prefill(ids, rid="sampled"))
        results = batcher.run()
        np.testing.assert_array_equal(
            results["greedy"], np.asarray(pipe.generate(ids, 6)))
        np.testing.assert_array_equal(
            results["sampled"],
            np.asarray(pipe.generate(ids, 5, temperature=0.9, seed=6)))
        snap = plane.fleet.snapshot()
        assert snap["leases"]["shipped"] == 2
        assert snap["leases"]["fallback"] == 0
        assert snap["zombies_dropped_total"] == 0
    finally:
        plane.close()


# ---------------------------------------------------------------------------
# the ship fault matrix
# ---------------------------------------------------------------------------

def test_crc_corrupt_ship_bounded_resend_then_success(pipe, prefill_pipe):
    """CRC-corrupt ship frame -> bounded re-ship -> success: the first
    ack's KV payload takes a flipped bit (the chaos corrupt@K hook,
    below the integrity layer), decode_kv_ship raises WireCorruptError,
    and the fleet re-leases — the second, clean ship installs with full
    token parity."""
    plane = _ShipPlane(prefill_pipe, crc=True)
    try:
        # corrupt exactly the NEXT send from the worker: its first ack
        plane.ctxs[1].send_retries = 0
        plane.ctxs[1]._corrupt_next_send = True
        rng = np.random.default_rng(43)
        ids = rng.integers(0, 100, size=(1, 6))
        handle = plane.fleet.prefill(ids, rid="crc")
        kv = _backend(pipe)
        batcher = ContinuousBatcher(pipe, kv=kv)
        batcher.submit("crc", ids, new_tokens=5, shipped=handle)
        np.testing.assert_array_equal(
            batcher.run()["crc"], np.asarray(pipe.generate(ids, 5)))
        snap = plane.fleet.snapshot()
        assert snap["ship_corrupt_total"] == 1
        assert snap["leases"]["corrupt_retry"] == 1
        assert snap["leases"]["shipped"] == 1
    finally:
        plane.close()


def test_resend_exhaustion_degrades_to_colocated_with_parity(
        pipe, prefill_pipe):
    """Every ship corrupt -> retry budget exhausted -> the caller falls
    back to COLOCATED prefill (submit without `shipped`) and the tokens
    still match solo generate() exactly — the request survives, only
    the isolation degrades (the serving layer's PrefillUnavailable
    contract)."""
    plane = _ShipPlane(prefill_pipe, crc=True, max_attempts=2)
    try:
        wctx = plane.ctxs[1]
        orig_send = wctx.send_tensors

        def corrupt_every_send(dst, tensors, channel=0, **kw):
            wctx._corrupt_next_send = True
            return orig_send(dst, tensors, channel=channel, **kw)

        wctx.send_tensors = corrupt_every_send
        rng = np.random.default_rng(47)
        ids = rng.integers(0, 100, size=(1, 8))
        kw = {}
        try:
            kw["shipped"] = plane.fleet.prefill(ids, rid="exhaust")
        except PrefillUnavailable:
            pass        # the serving layer's colocated fallback
        assert "shipped" not in kw, "corrupt ships should have exhausted"
        kv = _backend(pipe)
        batcher = ContinuousBatcher(pipe, kv=kv)
        batcher.submit("exhaust", ids, new_tokens=4, **kw)
        np.testing.assert_array_equal(
            batcher.run()["exhaust"], np.asarray(pipe.generate(ids, 4)))
        snap = plane.fleet.snapshot()
        assert snap["leases"]["fallback"] == 1
        assert snap["ship_corrupt_total"] == 2      # one per attempt
    finally:
        plane.close()


def test_worker_death_mid_lease_redispatches_to_survivor(
        pipe, prefill_pipe):
    """Prefill-peer death: rank 1 swallows its lease (no ack) and dies;
    the fleet resolves the stranded lease IMMEDIATELY (no full timeout
    burn), re-dispatches to surviving rank 2, and the request completes
    token-identically."""
    plane = _ShipPlane(prefill_pipe, n_workers=2, start=False,
                       lease_timeout_s=60.0,
                       heartbeat_interval=0.3, heartbeat_miss=3)
    try:
        # workers beat the decode rank like the real CLI does — beat
        # SILENCE is how a black-holed peer's death is detectable at
        # all (it never sends data the decode reader could see drop)
        for wctx in plane.ctxs[1:]:
            wctx.start_heartbeat([0], interval=0.3, miss_threshold=10)
        # rank 1 black-holes leases (receives, never acks); rank 2 serves
        stop_hole = threading.Event()

        def black_hole():
            while not stop_hole.is_set():
                try:
                    plane.ctxs[1].recv_tensors(0, timeout=0.2,
                                               channel=fleet_mod.CH_LEASE)
                except (queue_mod.Empty, ConnectionError, OSError):
                    continue

        hole = threading.Thread(target=black_hole, daemon=True)
        hole.start()
        t2 = threading.Thread(target=plane.workers[1].run, daemon=True)
        t2.start()
        plane.threads.append(t2)
        # pin round-robin so the first dispatch lands on doomed rank 1
        plane.fleet._rr = 1
        rng = np.random.default_rng(53)
        ids = rng.integers(0, 100, size=(1, 6))
        killer_fired = threading.Event()

        def kill_rank1():
            # wait until the lease is in flight on rank 1, then die
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                snap = plane.fleet.snapshot()
                if snap["in_flight"] >= 1:
                    break
                time.sleep(0.05)
            time.sleep(0.2)     # let the black hole swallow the lease
            stop_hole.set()
            plane.ctxs[1].shutdown()
            killer_fired.set()

        killer = threading.Thread(target=kill_rank1, daemon=True)
        killer.start()
        t0 = time.monotonic()
        handle = plane.fleet.prefill(ids, rid="death")
        took = time.monotonic() - t0
        assert killer_fired.wait(timeout=30)
        # re-dispatch was driven by the death, not the 60s lease timeout
        assert took < 45.0
        kv = _backend(pipe)
        batcher = ContinuousBatcher(pipe, kv=kv)
        batcher.submit("death", ids, new_tokens=4, shipped=handle)
        np.testing.assert_array_equal(
            batcher.run()["death"], np.asarray(pipe.generate(ids, 4)))
        snap = plane.fleet.snapshot()
        assert snap["leases"]["redispatched"] >= 1
        assert snap["leases"]["shipped"] == 1
        assert 1 in snap["dead"]
    finally:
        plane.close()


def test_readmitted_rank_takes_leases_again(pipe, prefill_pipe):
    """The other half of a worker's death: when its respawn joins (the
    transport's rejoin callback, epoch + 1) the fleet readmits the rank,
    `dead` empties, and the round-robin lands leases on it again, with
    the same tokens as before the fault."""
    plane = _ShipPlane(prefill_pipe, n_workers=2)
    try:
        fleet = plane.fleet
        rng = np.random.default_rng(59)
        ids = rng.integers(0, 100, size=(1, 6))
        want = np.asarray(pipe.generate(ids, 4))
        fleet._on_peer_death(1)
        assert fleet.snapshot()["dead"] == [1]
        assert fleet.live_ranks() == frozenset({2})
        served = []
        dispatch = fleet._dispatch_once

        def recording(ls, ids_t):
            served.append(ls.rank)
            return dispatch(ls, ids_t)

        fleet._dispatch_once = recording
        handles = [fleet.prefill(ids, rid=f"down{i}") for i in range(2)]
        assert served == [2, 2]             # nothing sent to the dead rank
        fleet._on_peer_rejoin(1, epoch=1)
        snap = fleet.snapshot()
        assert snap["dead"] == [] and snap["live"] == [1, 2]
        handles += [fleet.prefill(ids, rid=f"back{i}") for i in range(2)]
        assert sorted(served[2:]) == [1, 2]     # the readmitted rank serves
        kv = _backend(pipe)
        for i, handle in enumerate(handles):
            batcher = ContinuousBatcher(pipe, kv=kv)
            batcher.submit(i, ids, new_tokens=4, shipped=handle)
            np.testing.assert_array_equal(batcher.run()[i], want)
        snap = fleet.snapshot()
        assert snap["leases"]["shipped"] == 4 and snap["in_flight"] == 0
        assert kv.sweep_orphans(set()) == 0
        assert kv.pool.stats()["leaked"] == 0
    finally:
        plane.close()


def test_zombie_ack_stale_attempt_is_fenced():
    """A ship ack for a re-dispatched lease (stale attempt number) or an
    unknown lease must DROP, never resolve — the lease fence above the
    transport's epoch fence. Exercised against a stub context so the
    fence logic is isolated from socket timing."""

    class _StubCtx:
        CONNECT_TIMEOUT = 60.0

        def register_peer_death_handler(self, h):
            pass

        def register_peer_rejoin_handler(self, h):
            pass

        def stop_heartbeat(self):
            pass

        def recv_tensors(self, src, timeout=None, channel=0):
            raise queue_mod.Empty

    fleet = RemotePrefillFleet(_StubCtx(), ranks=[1], dtype=jnp.float32,
                               registry=prom.Registry())
    try:
        # unknown lease id: zombie
        fleet._resolve({"lease_id": 99, "attempt": 1, "status": 0}, [])
        assert fleet.snapshot()["zombies_dropped_total"] == 1
        # stale attempt: the lease moved on to attempt 2
        ls = fleet_mod._Lease(5, 2, 1, "r5")
        with fleet._lock:
            fleet._leases[5] = ls
        fleet._resolve({"lease_id": 5, "attempt": 1, "status": 0},
                       [np.zeros(3)])
        assert not ls.event.is_set(), "stale ack resolved a live lease"
        assert fleet.snapshot()["zombies_dropped_total"] == 2
        # the CURRENT attempt resolves normally
        fleet._resolve({"lease_id": 5, "attempt": 2, "status": 0},
                       [np.zeros(3)])
        assert ls.event.is_set() and ls.tensors is not None
        # ...and a second (duplicate) ack for the now-resolved lease is
        # a zombie again, not a double-resolution
        fleet._resolve({"lease_id": 5, "attempt": 2, "status": 0},
                       [np.ones(3)])
        assert fleet.snapshot()["zombies_dropped_total"] == 3
        np.testing.assert_array_equal(ls.tensors[0], np.zeros(3))
    finally:
        fleet.close()


def test_cancelled_lease_skipped_not_executed(prefill_pipe):
    """A lease the decode side cancelled (it timed out and was
    re-dispatched elsewhere) must be SKIPPED by the worker, not run
    into a zombie ack — cancels ride their own channel so they can
    overtake the stale lease they exist to stop."""
    ctxs = _make_contexts(2)
    calls = []

    class _Spy:
        cache_bits = 0
        dtype = prefill_pipe.dtype

        def _prefill(self, ids):
            calls.append(np.asarray(ids).shape)
            return prefill_pipe._prefill(ids)

    worker = PrefillWorkerLoop(_Spy(), ctxs[1])
    t = threading.Thread(target=worker.run, daemon=True)
    try:
        # cancel for lease 7 arrives BEFORE the lease (worker not yet
        # running, both frames queued), then lease 9 un-cancelled
        ctxs[0].send_tensors(1, [fleet_mod.cancel_header(7)],
                             channel=fleet_mod.CH_CANCEL)
        ctxs[0].send_tensors(
            1, [fleet_mod.lease_header(7, 1, 0, False, 1000),
                np.zeros((1, 4), np.int64)], channel=fleet_mod.CH_LEASE)
        ctxs[0].send_tensors(
            1, [fleet_mod.lease_header(9, 1, 0, False, 1000),
                np.ones((1, 5), np.int64)], channel=fleet_mod.CH_LEASE)
        t.start()
        # lease 9's ack arrives; lease 7 never produced one
        tensors = ctxs[0].recv_tensors(1, timeout=60.0,
                                       channel=fleet_mod.CH_SHIP)
        ack = fleet_mod.parse_ack_header(tensors[0])
        assert ack["lease_id"] == 9 and ack["status"] == fleet_mod.ACK_OK
        assert worker.leases_cancelled == 1
        assert calls == [(1, 5)], "the cancelled lease ran a prompt pass"
    finally:
        worker.stop()
        t.join(timeout=10)
        for c in ctxs:
            c.shutdown()


def test_worker_error_ack_counts_redispatch(pipe, prefill_pipe):
    """A worker that FAILS the prompt pass acks with an error status
    (silence would cost the full lease timeout); the fleet re-dispatches
    and, with no healthy alternative behavior, falls back."""
    plane = _ShipPlane(prefill_pipe, max_attempts=2)
    try:
        # poison the worker's prefill
        plane.workers[0].pipe = _Boom()
        with pytest.raises(PrefillUnavailable):
            plane.fleet.prefill(np.zeros((1, 4), np.int64), rid="boom")
        snap = plane.fleet.snapshot()
        # max_attempts=2: ONE re-dispatch actually happened (the final
        # failed attempt re-dispatches nothing — it falls back)
        assert snap["leases"]["redispatched"] == 1
        assert snap["leases"]["fallback"] == 1
    finally:
        plane.close()


class _Boom:
    cache_bits = 0

    def _prefill(self, ids):
        raise RuntimeError("poisoned prompt")


# ---------------------------------------------------------------------------
# chaos acceptance: kill a REAL prefill worker process mid-burst
# ---------------------------------------------------------------------------

@pytest.mark.fleet
def test_chaos_prefill_process_killed_midburst_all_requests_survive(
        pipe, tmp_path):
    """THE acceptance gate (ISSUE 15): two real prefill worker PROCESSES
    over DCN sockets; a burst of requests is in flight when one worker
    is SIGKILLed. Every request completes (re-dispatch to the survivor
    or colocated fallback), tokens are identical to solo runs on pinned
    seeds, and the page pool accounts for every page afterwards (zero
    leaks after the orphan sweep)."""
    world = 3
    addrs = [("127.0.0.1", p) for p in _free_ports(world)]
    addr_arg = ",".join(f"h:{p}".replace("h", "127.0.0.1")
                        for _, p in addrs)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               DCN_CONNECT_TIMEOUT="30")
    # a worker's log goes to a file: nobody reads a pipe while the burst
    # runs, it holds 64 KB, and a worker that has logged that much blocks
    logs = [open(tmp_path / f"worker{r}.log", "w") for r in (1, 2)]
    workers = [
        subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tools",
                                          "prefill_worker.py"),
             str(r), str(world), "--dcn-addrs", addr_arg, "-m", MODEL,
             "-pt", "1,4,5,8", "--max-len", str(MAX_LEN),
             "-t", "float32", "--heartbeat-interval", "0.5"],
            env=env, text=True, stdout=log, stderr=subprocess.STDOUT)
        for r, log in zip((1, 2), logs)]
    ctx = dcn.DistDcnContext(world, 0, addrs)
    ctx.init()
    # heartbeats make the SIGKILL detectable in ~2s (a killed worker
    # that never acked has no data conn whose drop rank 0 could see),
    # so leases leave the dead rank's rotation instead of each burning
    # the full lease timeout
    fleet = RemotePrefillFleet(ctx, ranks=[1, 2], dtype=pipe.dtype,
                               lease_timeout_s=10.0,
                               heartbeat_interval=0.5, heartbeat_miss=4,
                               registry=prom.Registry())
    kv = _backend(pipe, n_pages=24, page_size=4)
    batcher = ContinuousBatcher(pipe, kv=kv)
    try:
        rng = np.random.default_rng(59)
        prompts = [rng.integers(0, 100, size=(1, 6)) for _ in range(6)]
        lock = threading.Lock()
        shipped = {}

        def prefill_one(i):
            kw = {}
            try:
                kw["shipped"] = fleet.prefill(prompts[i], rid=f"b{i}")
            except PrefillUnavailable:
                pass       # colocated fallback: submit without shipped
            with lock:
                shipped[i] = kw

        threads = [threading.Thread(target=prefill_one, args=(i,),
                                    daemon=True) for i in range(6)]
        for t in threads[:2]:
            t.start()
        # let the first leases go out, then KILL worker rank 1 mid-burst
        time.sleep(0.3)
        os.kill(workers[0].pid, signal.SIGKILL)
        for t in threads[2:]:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive(), "a prefill call never returned"
        for i in range(6):
            batcher.submit(i, prompts[i], new_tokens=4,
                           **shipped.get(i, {}))
        results = batcher.run()
        assert len(results) == 6, "a request was lost to the fault"
        for i in range(6):
            np.testing.assert_array_equal(
                results[i], np.asarray(pipe.generate(prompts[i], 4)))
        # zero leaked pages: the orphan sweep (liveness = nothing live)
        # finds nothing to reclaim, and pool accounting closes exactly
        assert kv.sweep_orphans(set()) == 0
        assert kv.pool.stats()["leaked"] == 0
        cached = kv.trie.stats()["pages_cached"]
        assert kv.pool.free_pages + cached == kv.pool.n_pages
        snap = fleet.snapshot()
        assert 1 in snap["dead"]
        assert snap["leases"]["shipped"] >= 1
    finally:
        fleet.close()
        ctx.shutdown()
        for w in workers:
            if w.poll() is None:
                os.kill(w.pid, signal.SIGKILL)
            w.wait()
        for log in logs:
            log.close()


# ---------------------------------------------------------------------------
# the worker supervisor: respawn, epochs, retirement (tools/serve.py)
# ---------------------------------------------------------------------------

_STANDIN_WORKER = """
import os, sys, time
rank, log = sys.argv[1], sys.argv[2]
with open(log, "a") as fh:
    fh.write(f"{rank} {os.environ['DCN_EPOCH']} "
             f"{os.environ.get('DCN_CHAOS', '-')}\\n")
print(f"prefill worker rank {rank} ready", flush=True)
time.sleep(600)
"""


def _wait_for(pred, what, budget=60.0):
    end = time.monotonic() + budget
    while time.monotonic() < end:
        got = pred()
        if got:
            return got
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.fixture
def standin_fleet(tmp_path, monkeypatch):
    """A `PrefillWorkerSupervisor` over two stand-in workers that say the
    ready line and sleep: the supervisor's own lifecycle, without a model
    build a process (a respawn is cross-process by nature)."""
    from tools import serve as serve_mod
    script = tmp_path / "worker.py"
    script.write_text(_STANDIN_WORKER)
    log = tmp_path / "incarnations.log"
    monkeypatch.setenv("PIPEEDGE_PREFILL_CHAOS", "kill@2")
    sups = []

    def make(**kw):
        sup = serve_mod.PrefillWorkerSupervisor(
            [str(script), str(log)], ranks=(1, 2), **kw)
        sups.append(sup)
        sup.wait_ready(timeout=60)
        return sup

    make.incarnations = lambda: [tuple(line.split()) for line in
                                 log.read_text().splitlines()]
    yield make
    for sup in sups:
        sup.stop()


@pytest.mark.fleet
def test_supervisor_respawns_a_killed_worker_at_the_next_epoch(
        standin_fleet):
    """A SIGKILLed worker comes back as a new process carrying
    DCN_EPOCH + 1 (what lets its JOIN through the decode side's death
    fence) and says ready again; the chaos spec armed its first
    incarnation only; the other rank is not touched."""
    sup = standin_fleet()
    before = sup.snapshot()
    assert {r: v["epoch"] for r, v in before.items()} == {"1": 0, "2": 0}
    os.kill(before["1"]["pid"], signal.SIGKILL)
    def respawned():
        snap = sup.snapshot()
        one = snap.get("1", {})
        return snap if one.get("epoch") == 1 and one["alive"] else None

    after = _wait_for(respawned, "the respawn of rank 1")
    assert after["1"]["pid"] != before["1"]["pid"]
    assert after["2"] == before["2"]
    sup.wait_ready(timeout=60)
    _wait_for(lambda: len(standin_fleet.incarnations()) == 3,
              "the respawn's line")
    assert sorted(standin_fleet.incarnations()) == [
        ("1", "0", "kill@2"), ("1", "1", "-"), ("2", "0", "-")]


@pytest.mark.fleet
def test_supervisor_without_respawn_lets_a_dead_worker_go(standin_fleet):
    sup = standin_fleet(respawn=False)
    os.kill(sup.snapshot()["2"]["pid"], signal.SIGKILL)
    _wait_for(lambda: "2" not in sup.snapshot(), "rank 2's record to go")
    time.sleep(3 * sup.RESPAWN_DELAY_S)
    assert set(sup.snapshot()) == {"1"}
    assert len(standin_fleet.incarnations()) == 2


@pytest.mark.fleet
def test_supervisor_retired_rank_stays_down_and_rejoins_at_epoch_plus_one(
        standin_fleet):
    """Scale-in then scale-out: a retired rank is not resurrected by the
    watch loop, and bringing it back continues its epoch sequence, so the
    rejoin is fenced against the retired incarnation like a respawn."""
    sup = standin_fleet()
    assert sup.retire_rank(2) and not sup.retire_rank(2)
    time.sleep(3 * sup.RESPAWN_DELAY_S)
    assert set(sup.snapshot()) == {"1"} and sup.ranks == (1,)
    assert sup.add_rank() == 2              # the lowest retired id first
    sup.wait_ready(timeout=60)
    snap = sup.snapshot()
    assert snap["2"]["epoch"] == 1 and snap["2"]["alive"]
    assert snap["1"]["epoch"] == 0
    with pytest.raises(ValueError, match="already active"):
        sup.add_rank(2)
