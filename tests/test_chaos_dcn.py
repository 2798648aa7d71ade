"""Chaos acceptance for the fault-tolerance layer: loopback DCN fleets with
deterministic fault injection (DCN_CHAOS, pipeedge_tpu/comm/chaos.py).

The quick (not-slow) pair is the CI chaos smoke: kill a stage rank
mid-round and recover via failover; kill with no spare capacity and abort
naming the dead rank. The full kill/delay/hang matrix — including the
bit-identical replay comparison against a no-fault run — is `slow`."""
import json
import os
import signal
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.fleet   # every test spawns OS-process fleets

_MODEL = "pipeedge/test-tiny-vit"


def _free_ports(n):
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _run_chaos_fleet(tmp_path, world, chaos=None, victim=1, extra=(),
                     batch=24, timeout=240, env_extra=None,
                     worker_exit_s=60):
    """Launch a `world`-rank failover-mode fleet, arming `chaos` in the
    victim's env (`env_extra` lands in EVERY rank's env). Returns
    (data rc, data output, [worker outputs])."""
    addrs = ",".join(f"127.0.0.1:{p}" for p in _free_ports(world))
    common = [sys.executable, os.path.join(REPO, "runtime.py")]
    opts = ["-c", "dcn", "--platform", "cpu", "-m", _MODEL,
            "-b", str(batch), "-u", "4", "-pt", "1,4,5,8", "-q", "0,0",
            "-r", "0,1", "--dcn-addrs", addrs, "--sched-timeout", "120",
            "--on-peer-death", "failover", *extra]
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               DCN_CONNECT_TIMEOUT="30", **(env_extra or {}))
    dirs = []
    for r in range(world):
        d = tmp_path / f"rank{r}"
        d.mkdir(parents=True, exist_ok=True)
        dirs.append(d)
    # a worker's log goes to a file: it outlives a worker that has to be
    # killed (a restarted incarnation the fleet finished without), and no
    # pipe fills while rank 0 runs
    workers, logs = [], []
    for r in range(1, world):
        wenv = dict(env, DCN_CHAOS=chaos) if (chaos and r == victim) \
            else env
        logs.append(open(dirs[r] / "worker.log", "w+"))
        workers.append(subprocess.Popen(
            common + [str(r), str(world)] + opts, cwd=dirs[r], env=wenv,
            text=True, stdout=logs[-1], stderr=subprocess.STDOUT))
    try:
        data = subprocess.run(common + ["0", str(world)] + opts,
                              cwd=dirs[0], env=env, capture_output=True,
                              text=True, timeout=timeout)
        for w in workers:
            try:
                w.wait(timeout=worker_exit_s)
            except subprocess.TimeoutExpired:
                pass
    finally:
        for w in workers:
            try:
                # SIGKILL, not terminate: a hang-chaos victim is SIGSTOPped
                # and ignores everything else
                os.kill(w.pid, signal.SIGKILL)
            except OSError:
                pass
            w.wait()
        wouts = []
        for log in logs:
            log.seek(0)
            wouts.append(log.read())
            log.close()
    return data, wouts, dirs


def test_chaos_smoke_kill_stage_failover(tmp_path):
    """CI chaos smoke: kill the last stage at its 3rd result send; the
    spare rank takes the stage over, unacknowledged microbatches replay,
    and the run completes with every result delivered exactly once."""
    data, wouts, dirs = _run_chaos_fleet(
        tmp_path, world=3, chaos="kill@3",
        extra=["--save-results", "results.npz"])
    assert data.returncode == 0, data.stdout + data.stderr
    out = data.stdout + data.stderr
    assert "entering failover" in out
    assert "moves rank 1 -> 2" in out
    assert "replaying" in out and "unacknowledged" in out
    assert "latency_sec=" in data.stdout
    # the victim died to the chaos kill; the spare rebuilt stage 1
    assert "chaos: killing this process" in wouts[0]
    assert "stage 1: layers [5, 8]" in wouts[1]
    # all 6 microbatches delivered exactly once
    results = np.load(dirs[0] / "results.npz")
    assert len(results.files) == 6


def test_chaos_no_spare_capacity_aborts_naming_rank(tmp_path):
    """Failover mode with nothing to fail over TO: the fleet must still
    abort cleanly, naming the dead rank (the pre-failover semantics)."""
    data, wouts, _ = _run_chaos_fleet(tmp_path, world=2, chaos="kill@2",
                                      batch=16)
    assert data.returncode not in (None, 0)
    out = data.stdout + data.stderr
    assert "no spare capacity" in out and "rank 1 died" in out


def test_chaos_restart_rejoins_and_heals(tmp_path):
    """CI chaos-restart smoke (kill -> failover -> restart -> heal): the
    last stage dies at its 3rd send and re-execs 1.5s later as a new
    incarnation (DCN_EPOCH+1). The fleet fails over to a spare and
    replays; the restarted rank passes the JOIN admission handshake
    (rejoin event at the data rank); and with --on-peer-rejoin heal the
    pre-failure partition is restored at a round boundary — the final
    partition runs on the ORIGINAL ranks, every round's results exactly
    once.

    Was flaky (fails ~1 in 3 on the pristine tree) at a time when three
    rounds outlasted the restart: when detection of
    the death ran late enough that the restarted incarnation's JOIN was
    admitted FIRST, the victim moved dead_ranks -> benched_ranks before
    the round loop's 0.5s poll ever saw a dead scheduled rank, so
    `death_hits_schedule()` stayed false, the failover re-plan never
    ran, and the round waited out the full --sched-timeout for
    microbatches that died with the old incarnation ("pipeline
    delivered 2/16 results within 120.0s"). Fixed in runtime.py:
    `death_hits_schedule` now also counts a SCHEDULED rank that sits in
    benched_ranks while a death episode is open — a freshly rejoined
    incarnation holds no stage state, so the round must fail over to a
    spare either way (the heal then restores it at the boundary)."""
    # rounds enough to outlast the restart: the new incarnation is back 3 to
    # 4 s after the death (1.5 s of delay, then an interpreter and its
    # imports) and a round of this fleet takes 0.45 s, so three rounds were
    # over before it had announced itself and the run then showed no rejoin
    # (every run of the driver's); a heal needs a boundary after that
    rounds = 16
    data, wouts, dirs = _run_chaos_fleet(
        tmp_path, world=4, chaos="restart@3:1500", batch=16,
        extra=["--rounds", str(rounds), "--on-peer-rejoin", "heal",
               "--save-results", "results.npz"], worker_exit_s=20)
    out = data.stdout + data.stderr
    assert data.returncode == 0, out
    # the failover leg ran (spare took the stage over)
    assert "moves rank 1 -> 2" in out, out
    # the restarted incarnation was admitted exactly once...
    assert out.count("rejoin_rank=1") == 1, out
    assert "epoch=1" in out, out
    # ...and the heal restored the pre-failure placement with a finite
    # time-to-full-capacity
    assert "heal_round=" in out, out
    heal_line = [ln for ln in data.stdout.splitlines()
                 if ln.startswith("heal_round=")][0]
    assert "ranks=0,1" in heal_line
    assert "time_to_full_capacity_s=" in heal_line
    # the victim really died and came back as epoch 1
    assert "chaos: killing this process" in wouts[0]
    assert "re-exec as epoch 1" in wouts[0]
    assert "JOIN announced" in wouts[0]
    # 4 microbatches a round, exactly once each
    results = np.load(dirs[0] / "results.npz")
    assert len(results.files) == 4 * rounds


@pytest.mark.slow
def test_chaos_restart_heal_bit_identical(tmp_path):
    """The healed run's outputs are bit-identical to a fault-free run of
    the same 3 rounds: spare substitution keeps the partition, the heal
    restores the original placement, and the epoch-aware ledger delivers
    every microbatch exactly once."""
    fault, _, fdirs = _run_chaos_fleet(
        tmp_path / "fault", world=4, chaos="restart@3:1500", batch=16,
        extra=["--rounds", "3", "--on-peer-rejoin", "heal",
               "--save-results", "results.npz"])
    clean, _, cdirs = _run_chaos_fleet(
        tmp_path / "clean", world=4, chaos=None, batch=16,
        extra=["--rounds", "3", "--on-peer-rejoin", "heal",
               "--save-results", "results.npz"])
    assert fault.returncode == 0, fault.stdout + fault.stderr
    assert clean.returncode == 0, clean.stdout + clean.stderr
    got = np.load(fdirs[0] / "results.npz")
    want = np.load(cdirs[0] / "results.npz")
    assert sorted(got.files) == sorted(want.files)
    for k in got.files:
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(got[k], want[k])


@pytest.mark.slow
def test_chaos_restart_spare_mode_keeps_substitution(tmp_path):
    """--on-peer-rejoin spare: the restarted rank is re-admitted as idle
    capacity but its old stage STAYS on the substitute — no heal line,
    later rounds keep the failed-over placement, results exactly once."""
    data, _, dirs = _run_chaos_fleet(
        tmp_path, world=4, chaos="restart@3:1500", batch=16,
        extra=["--rounds", "3", "--on-peer-rejoin", "spare",
               "--save-results", "results.npz"])
    assert data.returncode == 0, data.stdout + data.stderr
    out = data.stdout + data.stderr
    assert "rejoin_rank=1" in out
    assert "heal_round=" not in out
    assert "moves rank 1 -> 2" in out
    results = np.load(dirs[0] / "results.npz")
    assert len(results.files) == 12


@pytest.mark.slow
def test_chaos_flap_survived_with_grace(tmp_path):
    """flap@K:MS inside every rank's reconnect-grace window: a network
    blip, not a death — the run completes with no failover and no
    rejoin (same incarnation throughout), results exactly once."""
    data, _, dirs = _run_chaos_fleet(
        tmp_path, world=3, chaos="flap@2:400", batch=16,
        extra=["--save-results", "results.npz"],
        env_extra={"DCN_RECONNECT_GRACE": "5", "DCN_SEND_RETRIES": "3"})
    assert data.returncode == 0, data.stdout + data.stderr
    out = data.stdout + data.stderr
    assert "chaos: flapping" not in out          # victim's log, not data's
    assert "entering failover" not in out
    assert "rejoin_rank=" not in out
    results = np.load(dirs[0] / "results.npz")
    assert len(results.files) == 4


@pytest.mark.slow
def test_chaos_kill_replay_bit_identical(tmp_path):
    """The exactly-once guarantee, bitwise: a killed-and-failed-over run's
    results are identical to a no-fault run's (same partition on the
    substituted rank, dedupe by microbatch id, in-order delivery)."""
    fault, _, fdirs = _run_chaos_fleet(
        tmp_path / "fault", world=3, chaos="kill@3",
        extra=["--save-results", "results.npz"])
    clean, _, cdirs = _run_chaos_fleet(
        tmp_path / "clean", world=3, chaos=None,
        extra=["--save-results", "results.npz"])
    assert fault.returncode == 0, fault.stdout + fault.stderr
    assert clean.returncode == 0, clean.stdout + clean.stderr
    got = np.load(fdirs[0] / "results.npz")
    want = np.load(cdirs[0] / "results.npz")
    assert sorted(got.files) == sorted(want.files)
    for k in got.files:
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(got[k], want[k])


@pytest.mark.slow
def test_chaos_hang_detected_by_heartbeat(tmp_path):
    """SIGSTOP a stage rank: its sockets stay open, so only the liveness
    plane (missed heartbeats) can detect it — then failover proceeds as
    for a closed-socket death."""
    data, wouts, _ = _run_chaos_fleet(
        tmp_path, world=3, chaos="hang@3",
        extra=["--heartbeat-interval", "0.5", "--heartbeat-miss", "8"])
    assert data.returncode == 0, data.stdout + data.stderr
    out = data.stdout + data.stderr
    assert "latency_sec=" in data.stdout
    assert "moves rank 1 -> 2" in out
    # SOME survivor detected the hang via missed beats (the hung process
    # never closed a socket)
    fleet_out = out + "".join(wouts)
    assert "missed" in fleet_out and "heartbeats" in fleet_out


@pytest.mark.slow
def test_chaos_delay_is_survived_without_failover(tmp_path):
    """A slow link (every send delayed) is degradation, not death: the
    run completes with no failover."""
    data, _, _ = _run_chaos_fleet(tmp_path, world=3, chaos="delay@1:150",
                                  batch=16)
    assert data.returncode == 0, data.stdout + data.stderr
    assert "latency_sec=" in data.stdout
    assert "entering failover" not in data.stdout + data.stderr


@pytest.mark.slow
def test_chaos_tool_records_time_to_full_capacity(tmp_path):
    """tools/chaos_dcn.py restart experiment end to end: the JSON record
    carries the healing timeline (detect -> rejoin -> healed) with a
    finite time_to_full_capacity_s."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_dcn.py"),
         "--world", "4", "--victim", "1", "--chaos", "restart@3:1500",
         "--rounds", "3", "--on-peer-rejoin", "heal", "--expect", "heal",
         "-b", "16"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=REPO), cwd=tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["completed"] and not record["timed_out"]
    assert record["rejoin_s"] is not None and record["rejoin_s"] > 0
    assert record["heal_s"] is not None
    assert record["time_to_full_capacity_s"] is not None
    assert record["time_to_full_capacity_s"] > 0
    assert record["rejoin_mode"] == "heal"


@pytest.mark.slow
def test_chaos_tool_records_latencies(tmp_path):
    """tools/chaos_dcn.py end to end: runs the kill experiment, asserts
    recovery, and emits the detection/recovery-latency JSON record."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_dcn.py"),
         "--world", "3", "--victim", "1", "--chaos", "kill@3"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=REPO), cwd=tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["completed"] and not record["timed_out"]
    assert record["detect_s"] is not None and record["detect_s"] > 0
    assert record["recover_s"] is not None and record["recover_s"] > 0
    assert record["replayed"] >= 1
