"""`roofline_share.granite-summary` and `ssm_step_hbm_share.granite-summary`:
the two readers `granite4-h-micro.summary-batch` brings, on made-up
`observed` (a trace with and without an `ssm_step` operation, no trace at
all: they read nothing and do not raise) and in the traced line of the
cell's rehearsal."""
import json
import os

import pytest

from benchmark import costs_granite_hybrid as costs
from benchmark import manifest as rules
from benchmark import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ROOFLINE = "roofline_share.granite-summary"
KERNEL = "ssm_step_hbm_share.granite-summary"
CELL = "granite4-h-micro.summary-batch"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STEP_OP = "jit_decode_step/_ssm_step_f32_36_64_64_64_128_"


def _reader(name):
    return bench_run.load_reader(
        rules.reader_path(REPO, rules.load(REPO), name))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as file:
        return json.load(file)


def _observed(config, device_ops, busy_s=4.0, generations=2):
    """What the runner saw in a traced window of `generations` generations
    of a prefill and 63 steps at the cell's sizes."""
    return {"config": config, "rows": 64, "prompt_len": 512,
            "trace_new_tokens": 64, "trace_decode_steps": generations * 63,
            "peaks": PEAKS,
            "trace": {"window_s": busy_s * 1.002, "busy_s": busy_s,
                      "device_ops": device_ops, "idle_gaps": []}}


@pytest.mark.parametrize("name", [ROOFLINE, KERNEL])
def test_the_entries_name_the_cell_and_an_accepted_layer(name, source):
    manifest = rules.load(source)
    assert rules.problems(manifest, source) == []
    [entry] = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["moves"] == "tok_per_s" and entry["unit"] == "%"
    assert entry["source"] == "device_trace" and entry["better"] == "higher"
    assert entry["workloads"] == [CELL]
    beside = "roofline_share.nemotron-reason" if name == ROOFLINE \
        else "ssm_fused_share.nemotron-reason"
    [other] = [m for m in manifest["per_layer"] if m["name"] == beside]
    assert entry["layer"] == other["layer"]
    # the cell reads the chunked share off the counters the Mamba-2 mixer
    # shares (the fused share's list is pinned to one cell by an accepted
    # test, `test_benchmark_ssm_fused_reader.py`: the kernel's share above
    # reads nothing where no step took the kernel, which says as much)
    [metric] = [m for m in manifest["per_layer"]
                if m["name"] == "ssm_chunked_share.nemotron-reason"]
    assert CELL in metric["workloads"]


def test_the_kernels_share_is_its_bytes_over_its_self_time(config):
    read = _reader(KERNEL)
    # 126 steps x 36 layers, each 2 x 64 rows x 2 MiB; at the HBM's peak a
    # call takes 0.3277 ms: at 1.492 s of self time the share is 100%
    moved = 126 * 36 * 2 * 64 * 2097152
    assert moved == 126 * 36 * 2 * 64 * costs.layer_state_bytes_a_row(config)
    at_peak = moved / 819e9
    ops = [["jit_decode_step/_fusion_f32_3_64_8192_", 1.2],
           [STEP_OP, at_peak / 0.75], ["jit_decode_step/_copy", 0.1]]
    assert read(_observed(config, ops)) == pytest.approx(75.0)
    # the kernel's name in two programs (two buckets' steps): summed
    halves = [[STEP_OP, at_peak], ["jit_decode_step_1/_ssm_step_f32_", at_peak]]
    assert read(_observed(config, halves)) == pytest.approx(50.0)


@pytest.mark.parametrize("spoil", ["no-kernel", "no-trace", "no-steps"])
def test_without_the_operation_the_kernels_reader_reads_nothing(config,
                                                               spoil):
    """A program without the kernel (the jnp step: a backend without
    Mosaic, the parent of PR 48), a run that was not traced, a traced
    window without a step: None, never an exception."""
    read = _reader(KERNEL)
    observed = _observed(config, [["jit_decode_step/_fusion_f32_", 3.0]])
    if spoil == "no-trace":
        observed["trace"] = None
    if spoil == "no-steps":
        observed = _observed(config, [[STEP_OP, 1.0]], generations=0)
    assert read(observed) is None


def test_the_roofline_share_is_what_the_calls_need_over_busy_seconds(config):
    read = _reader(ROOFLINE)
    live = 512 + 64 / 2.0
    step_s = costs.decode_step_bytes(config, 64, live) / 819e9
    prefill_s = max(costs.prefill_flops(config, 64, 512) / 197e12,
                    costs.prefill_bytes(config, 64, 512) / 819e9)
    # a step is bound by its bytes (20.7 ms), a prompt by its FLOPs
    assert costs.decode_step_flops(config, 64, live) / 197e12 < step_s
    assert costs.prefill_flops(config, 64, 512) / 197e12 \
        > costs.prefill_bytes(config, 64, 512) / 819e9
    assert 0.0205 < step_s < 0.0210
    needed = 2 * prefill_s + 126 * step_s
    assert read(_observed(config, [], busy_s=needed / 0.6)) \
        == pytest.approx(60.0)
    assert read(_observed(config, [], busy_s=needed)) == pytest.approx(100.0)
    untraced = _observed(config, [])
    untraced["trace"] = None
    assert read(untraced) is None
    assert read(dict(_observed(config, []), trace_decode_steps=0)) is None


def test_the_rehearsed_cell_reports_what_the_cpu_can(tiny_root, run_cell):
    """On the CPU the trace has no device plane and no step takes the
    kernel: the traced line leaves both device metrics out and carries the
    shared counter's (every prompt position chunked)."""
    _, traced = run_cell(tiny_root, CELL, trace=True, seconds=0.5)
    assert traced["correct"] is True
    assert ROOFLINE not in traced["metrics"]
    assert KERNEL not in traced["metrics"]
    assert traced["metrics"]["ssm_chunked_share.nemotron-reason"]["value"] \
        == 100.0
