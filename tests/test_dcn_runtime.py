"""Multi-process runtime integration over the DCN transport: the reference's
`runtime.py RANK WORLDSIZE` deployment shape (one OS process per rank,
schedule broadcast via CMD_SCHED, results + CMD_STOP), on localhost."""
import os
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


import pytest

pytestmark = pytest.mark.fleet  # every test here spawns OS processes

def _free_ports(n):
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


class _OutReader:
    """Drain a subprocess's stdout on a thread so the test can poll for a
    marker without blocking on readline."""

    def __init__(self, proc):
        self.proc = proc
        self.lines = []
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        for line in self.proc.stdout:
            self.lines.append(line)

    def wait_for(self, needle, timeout):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if any(needle in line for line in self.lines):
                return True
            if self.proc.poll() is not None:
                self._thread.join(timeout=5)
                return any(needle in line for line in self.lines)
            time.sleep(0.05)
        return False

    def text(self):
        self._thread.join(timeout=5)
        return "".join(self.lines)



def _run_fleet(tmp_path, opts, world, env_extra=None, per_rank_dirs=False,
               data_timeout=300, script="runtime.py",
               rank_argv=lambda r, world: [str(r), str(world)]):
    """Launch a `world`-rank DCN fleet (workers as Popen, the data rank in
    the foreground), collect everyone's output.

    Returns (data CompletedProcess, [worker stdout by rank], rank_dirs).
    `opts` excludes --dcn-addrs (allocated here). Worker processes are
    always killed on exit. `script` (repo-relative) + `rank_argv(r, world)`
    cover CLIs with other rank conventions (e.g. tools/generate.py)."""
    addrs = ",".join(f"127.0.0.1:{p}" for p in _free_ports(world))
    common = [sys.executable, os.path.join(REPO, script)]
    argv = opts + ["--dcn-addrs", addrs]
    env = dict(os.environ, PYTHONPATH=REPO, **(env_extra or {}))
    if per_rank_dirs:
        rank_dirs = []
        for r in range(world):
            d = tmp_path / f"rank{r}"
            d.mkdir()
            rank_dirs.append(d)
    else:
        rank_dirs = [tmp_path] * world
    # a worker's log goes to a file: a pipe nobody reads while rank 0 runs
    # holds 64 KB, and a worker that has logged that much blocks
    logs = [open(tmp_path / f"worker{r}.log", "w+")
            for r in range(1, world)]
    workers = [subprocess.Popen(common + rank_argv(r, world) + argv,
                                cwd=rank_dirs[r], env=env, stdout=log,
                                stderr=subprocess.STDOUT, text=True)
               for r, log in enumerate(logs, start=1)]
    try:
        data = subprocess.run(common + rank_argv(0, world) + argv,
                              cwd=rank_dirs[0], env=env, capture_output=True,
                              text=True, timeout=data_timeout)
        for w in workers:
            w.wait(timeout=60)
    finally:
        for w in workers:
            w.kill()
            w.wait()
        wouts = []
        for log in logs:
            log.seek(0)
            wouts.append(log.read())
            log.close()
    for r, (w, wout) in enumerate(zip(workers, wouts), start=1):
        assert w.returncode == 0, f"rank {r}:\n{wout}"
    return data, wouts, rank_dirs

def test_two_process_dcn_runtime_quantized_edge(tmp_path):
    data, wouts, _ = _run_fleet(
        tmp_path, ["-c", "dcn", "--platform", "cpu",
                   "-m", "pipeedge/test-tiny-vit", "-b", "16", "-u", "4",
                   "-pt", "1,4,5,8", "-q", "8,0", "-r", "0,1",
                   "--sched-timeout", "120"], world=2, data_timeout=240)
    assert data.returncode == 0, data.stdout + data.stderr
    assert "latency_sec=" in data.stdout
    assert "======= pipeedge/test-tiny-vit stage 1: layers [5, 8]" in wouts[0]


def test_two_process_dcn_adaptive_quant(tmp_path):
    """Adaptive quantization over DCN: rank 0 (stage 0) measures its own
    send window via the transport hooks and adapts its output-edge bitwidth;
    the bitwidth rides the wire header so rank 1 decodes without
    coordination (reference per-rank policy, runtime.py:121-216)."""
    data, wouts, rank_dirs = _run_fleet(
        tmp_path, ["-c", "dcn", "--platform", "cpu",
                   "-m", "pipeedge/test-tiny-vit", "-b", "24", "-u", "4",
                   "-pt", "1,4,5,8", "-q", "8,0", "-r", "0,1",
                   "--sched-timeout", "120"], world=2,
        env_extra={"ADAPTIVE_QUANT": "HEURISTIC", "SEND_CONSTRAINT": "100",
                   "WINDOW_SIZE": "3"}, per_rank_dirs=True, data_timeout=240)
    assert data.returncode == 0, data.stdout + data.stderr
    # the data rank hosts stage 0, whose policy adapts its output edge
    assert "Adaptive quantization" in data.stdout + data.stderr
    # transport hooks produced per-rank wire telemetry CSVs
    assert (rank_dirs[0] / "send.csv").exists()
    assert (rank_dirs[1] / "recv.csv").exists()


def test_peer_death_aborts_fleet(tmp_path):
    """Fault tolerance beyond the reference (whose RPC backpressure 'breaks
    down if the previous stage fails to send data afterward',
    rpc/__init__.py:83-86): kill a middle stage mid-run and assert the whole
    fleet stops deterministically — well before --sched-timeout — with every
    surviving rank raising a message naming the dead rank."""
    addrs = ",".join(f"127.0.0.1:{p}" for p in _free_ports(3))
    common = [sys.executable, os.path.join(REPO, "runtime.py")]
    opts = ["-c", "dcn", "--platform", "cpu",
            "-m", "pipeedge/test-tiny-vit", "-b", "1024", "-u", "4",
            "-pt", "1,2,3,5,6,8", "-q", "0,0,0", "-r", "0,1,2",
            "--dcn-addrs", addrs, "--sched-timeout", "600"]
    env = dict(os.environ, PYTHONPATH=REPO)

    def launch(rank):
        return subprocess.Popen(common + [str(rank), "3"] + opts,
                                cwd=tmp_path, env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    victim, survivor = launch(1), launch(2)
    victim_out, survivor_out = _OutReader(victim), _OutReader(survivor)
    data = launch(0)
    data_out = _OutReader(data)
    try:
        # the victim has its schedule and model built; data is about to flow
        assert victim_out.wait_for("stage 1: layers", 180), victim_out.text()
        victim.kill()
        # detection + CMD_STOP fan-out, NOT the 600s timeout
        data.wait(timeout=120)
        survivor.wait(timeout=60)
    finally:
        for proc in (victim, survivor, data):
            proc.kill()
    assert data.returncode not in (None, 0), data_out.text()
    assert "died" in data_out.text(), data_out.text()
    assert survivor.returncode not in (None, 0), survivor_out.text()
    assert "died" in survivor_out.text(), survivor_out.text()


def test_four_process_idle_rank_adaptive_quant(tmp_path):
    """4 ranks, 3-stage schedule: rank 3 is NOT in the schedule and must idle
    until CMD_STOP (reference model_cfg.py:154-159, runtime.py:456-460), while
    the scheduled ranks run a mixed-bitwidth quantized pipeline with the
    adaptive policy live on every edge's owner."""
    data, wouts, rank_dirs = _run_fleet(
        tmp_path, ["-c", "dcn", "--platform", "cpu",
                   "-m", "pipeedge/test-tiny-vit", "-b", "32", "-u", "4",
                   "-pt", "1,2,3,5,6,8", "-q", "8,4,0", "-r", "0,1,2",
                   "--sched-timeout", "180"], world=4,
        env_extra={"ADAPTIVE_QUANT": "HEURISTIC", "SEND_CONSTRAINT": "100",
                   "WINDOW_SIZE": "3"}, per_rank_dirs=True)
    assert data.returncode == 0, data.stdout + data.stderr
    assert "latency_sec=" in data.stdout
    assert "stage 1: layers [3, 5]" in wouts[0]
    assert "stage 2: layers [6, 8]" in wouts[1]
    assert "not in schedule; idling" in wouts[2]
    # stage 0 (data rank) and stage 1 both own quantized output edges whose
    # bitwidth the policy adapts on their measured send window
    assert "Adaptive quantization" in data.stdout + data.stderr
    assert "Adaptive quantization" in wouts[0]
    # per-rank wire telemetry from the transport hooks
    assert (rank_dirs[0] / "send.csv").exists()
    assert (rank_dirs[1] / "send.csv").exists()
    assert (rank_dirs[2] / "recv.csv").exists()


def test_live_reschedule_two_rounds(tmp_path):
    """Live re-scheduling over one DCN fleet: the reference DESIGNED this
    (CMD_SCHED lands on a queue any time, runtime.py:404-415) but its runtime
    consumes exactly one schedule. Here the data rank broadcasts a second,
    DIFFERENT partition at the run boundary and the same worker processes
    rebuild their stages and run again — ending on an empty CMD_SCHED."""
    data, wouts, _ = _run_fleet(
        tmp_path, ["-c", "dcn", "--platform", "cpu",
                   "-m", "pipeedge/test-tiny-vit", "-b", "16", "-u", "4",
                   "-pt", "1,4,5,8;1,2,3,8", "-q", "8,0;4,0", "-r", "0,1",
                   "--sched-timeout", "180"], world=2)
    assert data.returncode == 0, data.stdout + data.stderr
    # one latency report per round
    assert data.stdout.count("latency_sec=") == 2, data.stdout
    assert "re-schedule: broadcasting round 1" in data.stdout + data.stderr
    # the worker rebuilt its stage with the round-2 partition
    assert "stage 1: layers [5, 8]" in wouts[0]
    assert "stage 1: layers [3, 8]" in wouts[0]
    assert "empty CMD_SCHED; shutting down" in wouts[0]


def test_dcn_stage_tp_hierarchical(tmp_path):
    """Hierarchical parallelism the reference cannot express: pipeline
    stages span hosts over DCN (TCP) while each rank Megatron-TP-shards its
    stage's blocks over its local devices (--stage-tp). Numerical equality
    of the TP block against the plain block is covered by
    tests/test_tensor_parallel.py; this exercises the full runtime path."""
    data, wouts, _ = _run_fleet(
        tmp_path, ["-c", "dcn", "--platform", "cpu", "--stage-tp", "2",
                   "-m", "pipeedge/test-tiny-vit", "-b", "16", "-u", "4",
                   "-pt", "1,4,5,8", "-q", "8,0", "-r", "0,1",
                   "--sched-timeout", "180"], world=2,
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert data.returncode == 0, data.stdout + data.stderr
    assert "latency_sec=" in data.stdout
    assert "TP-sharded over 2 local devices" in data.stdout + data.stderr
    assert "TP-sharded over 2 local devices" in wouts[0]


def test_tp_stage_matches_plain_stage():
    """_make_tp_stage output == plain module_shard_factory stage output for
    both shard ends (embed+block and block+finalize)."""
    import argparse

    import jax.numpy as jnp
    import numpy as np

    import runtime as rt
    from pipeedge_tpu.models import registry

    rng = np.random.default_rng(0)
    for model, payload in (
            ("pipeedge/test-tiny-vit",
             jnp.asarray(rng.normal(size=(2, 3, 16, 16)), jnp.float32)),
            ("pipeedge/test-tiny-bert",
             jnp.asarray(rng.integers(0, 30, size=(2, 9)), jnp.int32))):
        args = argparse.Namespace(stage_tp=2, model_name=model,
                                  model_file=None)
        for l, r, stage in ((1, 4, 0), (5, 8, 1)):
            fn_ref, p_ref, _ = registry.module_shard_factory(
                model, None, l, r, stage=stage, dtype=jnp.float32)
            fn_tp, p_tp = rt._make_tp_stage(args, l, r, stage, jnp.float32,
                                            None)
            ref = np.asarray(fn_ref(p_ref, payload))
            got = np.asarray(fn_tp(p_tp, payload))
            np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
            payload = fn_ref(p_ref, payload)


def test_multi_round_shifting_fleet(tmp_path):
    """The whole control-plane feature matrix on one fleet: three schedule
    rounds where the partition, rank order, stage count, AND the idle set
    all change between rounds, with adaptive quantization and TP-sharded
    stages throughout. Round 2 runs single-stage on a rank that was idle in
    round 1; round 3 swaps the rank order of round 1."""
    data, wouts, _ = _run_fleet(
        tmp_path, ["-c", "dcn", "--platform", "cpu", "--stage-tp", "2",
                   "-m", "pipeedge/test-tiny-vit", "-b", "24", "-u", "4",
                   "-pt", "1,4,5,8;1,8;1,4,5,8", "-q", "8,0;0;4,0",
                   "-r", "0,1;2;1,0", "--sched-timeout", "180"], world=4,
        env_extra={"ADAPTIVE_QUANT": "HEURISTIC", "SEND_CONSTRAINT": "100",
                   "WINDOW_SIZE": "3",
                   "XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
        per_rank_dirs=True, data_timeout=420)
    assert data.returncode == 0, data.stdout + data.stderr
    assert data.stdout.count("latency_sec=") == 3, data.stdout
    for wout in wouts:
        assert "Traceback" not in wout, wout
    # rank 2 idles in round 1, runs the whole model in round 2
    assert "not in schedule; idling" in wouts[1]
    assert "stage 0: layers [1, 8]" in wouts[1]
    # rank 3 never appears in any schedule: idles all three rounds
    assert wouts[2].count("not in schedule; idling") == 3
