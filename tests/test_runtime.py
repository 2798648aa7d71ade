"""Runtime CLI integration tests (subprocess, 8 fake devices).

Covers the reference's primary usage patterns (README.md:75-79): single-node
degenerate, manual multi-stage partition, quantized edges, SPMD driver, and
scheduler-driven auto-partitioning.
"""
import os
import shutil
import subprocess
import sys

import pytest
import yaml

from pipeedge_tpu.sched.scheduler import _REPO_BUILD_PATHS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "pipeedge/test-tiny-vit"


pytestmark = pytest.mark.fleet  # every test here spawns OS processes

def _run(tmp_path, *extra, env_extra=None, timeout=300):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO)
    if env_extra:
        env.update(env_extra)
    cmd = [sys.executable, os.path.join(REPO, "runtime.py")] + list(extra)
    return subprocess.run(cmd, capture_output=True, env=env, cwd=str(tmp_path),
                          timeout=timeout, text=True)


def _throughput(proc) -> float:
    for line in proc.stdout.splitlines():
        if line.startswith("latency_sec="):
            return float(line.split("throughput_items_sec=")[1])
    raise AssertionError(f"no stats line in output:\n{proc.stdout}\n{proc.stderr}")


def test_single_stage_degenerate(tmp_path):
    proc = _run(tmp_path, "0", "1", "-m", MODEL, "-b", "4", "-u", "2")
    assert proc.returncode == 0, proc.stderr
    assert _throughput(proc) > 0
    # monitoring CSVs created per key
    assert (tmp_path / "shard.csv").exists()
    assert (tmp_path / "output.csv").exists()


def test_two_stage_host_with_quant(tmp_path):
    proc = _run(tmp_path, "0", "2", "-m", MODEL, "-pt", "1,4,5,8",
                "-q", "8,0", "-b", "4", "-u", "2")
    assert proc.returncode == 0, proc.stderr
    assert _throughput(proc) > 0


def test_midblock_partition_host(tmp_path):
    """Sublayer (mid-block) cuts: 2-tensor payload across the edge."""
    proc = _run(tmp_path, "0", "3", "-m", MODEL, "-pt", "1,1,2,5,6,8",
                "-b", "4", "-u", "2")
    assert proc.returncode == 0, proc.stderr


def test_spmd_driver(tmp_path):
    proc = _run(tmp_path, "0", "2", "-m", MODEL, "-pt", "1,4,5,8",
                "-c", "spmd", "-b", "4", "-u", "2")
    assert proc.returncode == 0, proc.stderr
    assert _throughput(proc) > 0


def test_spmd_falls_back_on_midblock_cut(tmp_path):
    proc = _run(tmp_path, "0", "2", "-m", MODEL, "-pt", "1,5,6,8",
                "-c", "spmd", "-b", "4", "-u", "2")
    assert proc.returncode == 0, proc.stderr
    assert "falling back to host driver" in proc.stderr + proc.stdout


def test_gpt2_host_and_spmd(tmp_path):
    """Causal-decoder family end-to-end through the runtime CLI: 2-stage
    host driver with a quantized edge, then the SPMD driver."""
    proc = _run(tmp_path, "0", "2", "-m", "pipeedge/test-tiny-gpt2",
                "-pt", "1,4,5,8", "-q", "8,0", "-b", "4", "-u", "2")
    assert proc.returncode == 0, proc.stderr
    assert _throughput(proc) > 0
    proc = _run(tmp_path, "0", "2", "-c", "spmd",
                "-m", "pipeedge/test-tiny-gpt2", "-pt", "1,4,5,8",
                "-b", "4", "-u", "2")
    assert proc.returncode == 0, proc.stderr
    assert _throughput(proc) > 0


def test_nonzero_rank_exits(tmp_path):
    proc = _run(tmp_path, "1", "2", "-m", MODEL)
    assert proc.returncode == 0


@pytest.mark.skipif(
    not (os.path.exists(_REPO_BUILD_PATHS[0]) or shutil.which("sched-pipeline")),
    reason="sched-pipeline binary not built")
def test_scheduler_driven_partition(tmp_path):
    # synthetic profile files for the tiny model over 2 identical chips
    n = 8
    models = {MODEL: {"layers": n, "parameters_in": 768,
                      "parameters_out": [1000] * n, "mem_MB": [1.0] * n}}
    types = {"chip": {"mem_MB": 1024, "bw_Mbps": 10000, "model_profiles": {
        MODEL: [{"dtype": "torch.float32", "batch_size": 2,
                 "time_s": [0.01] * n}]}}}
    devs = {"chip": ["0", "1"]}
    for fname, data in (("models.yml", models), ("device_types.yml", types),
                        ("devices.yml", devs)):
        with open(tmp_path / fname, "w") as f:
            yaml.safe_dump(data, f, default_flow_style=None)
    proc = _run(tmp_path, "0", "2", "-m", MODEL, "-u", "2", "-b", "4",
                "-sm", "models.yml", "-sdt", "device_types.yml",
                "-sd", "devices.yml")
    assert proc.returncode == 0, proc.stderr
    assert _throughput(proc) > 0


def test_per_edge_send_telemetry_csvs(tmp_path):
    """Each inter-stage edge gets its own send telemetry key/CSV with real
    wire bytes per microbatch: the 8-bit quantized edge 0
    reports far fewer Mbits than the raw mid-block edge 1."""
    import csv as csvmod
    proc = _run(tmp_path, "0", "3", "-m", MODEL, "-pt", "1,4,5,6,7,8",
                "-q", "8,0,0", "-b", "8", "-u", "2")
    assert proc.returncode == 0, proc.stderr
    rows = {}
    for key in ("send0", "send1", "send"):
        f = tmp_path / f"{key}.csv"
        assert f.exists(), f"missing {key}.csv"
        with open(f) as fh:
            rows[key] = list(csvmod.DictReader(fh))
    # 4 microbatches -> >=3 beats per edge (the first call starts the clock)
    assert len(rows["send0"]) >= 3
    assert len(rows["send0"]) == len(rows["send1"])
    w0 = float(rows["send0"][-1]["Work"])
    w1 = float(rows["send1"][-1]["Work"])
    assert 0 < w0 < w1 / 3


def test_adaptive_quant_heuristic(tmp_path):
    proc = _run(tmp_path, "0", "2", "-m", MODEL, "-pt", "1,4,5,8",
                "-q", "8,0", "-b", "12", "-u", "2",
                env_extra={"ADAPTIVE_QUANT": "HEURISTIC",
                           "SEND_CONSTRAINT": "100", "WINDOW_SIZE": "3"})
    assert proc.returncode == 0, proc.stderr
    assert "Adaptive quantization" in proc.stderr + proc.stdout


def test_adaptive_quant_controller(tmp_path):
    proc = _run(tmp_path, "0", "2", "-m", MODEL, "-pt", "1,4,5,8",
                "-q", "8,0", "-b", "12", "-u", "2",
                env_extra={"ADAPTIVE_QUANT": "CONTROLLER",
                           "SEND_CONSTRAINT": "50", "WINDOW_SIZE": "3"})
    assert proc.returncode == 0, proc.stderr
    assert "Adaptive quantization" in proc.stderr + proc.stdout


def test_runtime_spmd_dp_tp_mesh(tmp_path):
    """CLI spmd driver over a stages x dp x tp mesh (one XLA program)."""
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "runtime.py"), "0", "8",
         "--platform", "cpu", "-c", "spmd", "-m", "pipeedge/test-tiny-vit",
         "-b", "16", "-u", "4", "-pt", "1,4,5,8", "-q", "8,0",
         "--spmd-dp", "2", "--spmd-tp", "2"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "latency_sec=" in proc.stdout


def test_runtime_spmd_sp_mesh(tmp_path):
    """CLI spmd driver with sequence parallelism inside pipeline stages
    (ring attention over 'sp'; BERT synthetic tokens, seq divisible)."""
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "runtime.py"), "0", "4",
         "--platform", "cpu", "-c", "spmd", "-m", "pipeedge/test-tiny-bert",
         "-b", "8", "-u", "4", "-pt", "1,4,5,8", "--spmd-sp", "2"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "latency_sec=" in proc.stdout


def test_measure_rounds_reports_cold_and_warm(tmp_path):
    """--measure-rounds N re-runs the ubatch stream, printing a latency
    line per round (round 0 pays the XLA compiles) ahead of the final
    plain stats line; results/accuracy counting stays per-round exact."""
    proc = _run(tmp_path, "0", "1", "-m", MODEL, "-b", "4", "-u", "2",
                "--measure-rounds", "3")
    assert proc.returncode == 0, proc.stderr
    rounds = [line for line in proc.stdout.splitlines()
              if line.startswith("round=")]
    assert [line.split()[0] for line in rounds] == \
        ["round=0", "round=1", "round=2"]
    # the final plain line repeats the LAST round's numbers
    assert _throughput(proc) == float(
        rounds[-1].split("throughput_items_sec=")[1])
