"""`roofline_share.brumby-longgen`, `retention_step_hbm_share.brumby-longgen`
and `retention_chunked_share.brumby-longgen`: the three readers
`brumby-14b.longgen-batch` brings, on made-up `observed` (a trace with and
without a `retention_step` operation, no trace at all: they read nothing and
do not raise) and in the traced line of the cell's rehearsal."""
import json
import os

import pytest

from benchmark import costs_brumby as costs
from benchmark import manifest as rules
from benchmark import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ROOFLINE = "roofline_share.brumby-longgen"
KERNEL = "retention_step_hbm_share.brumby-longgen"
CHUNKED = "retention_chunked_share.brumby-longgen"
CELL = "brumby-14b.longgen-batch"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STEP_OP = "jit_decode_step/_retention_step_f32_10_8_8_128_8320_"


def _reader(name):
    return bench_run.load_reader(
        rules.reader_path(REPO, rules.load(REPO), name))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "brumby-14b-base.json")) as file:
        return json.load(file)


def _observed(config, device_ops, busy_s=4.0, generations=2):
    """What the runner saw in a traced window of `generations` generations
    of a prefill and 63 steps at the cell's sizes."""
    return {"config": config, "rows": 8, "prompt_len": 1024,
            "trace_new_tokens": 64, "trace_decode_steps": generations * 63,
            "peaks": PEAKS,
            "trace": {"window_s": busy_s * 1.002, "busy_s": busy_s,
                      "device_ops": device_ops, "idle_gaps": []}}


@pytest.mark.parametrize("name", [ROOFLINE, KERNEL, CHUNKED])
def test_the_entries_name_the_cell_and_their_layer(name, source):
    manifest = rules.load(source)
    assert rules.problems(manifest, source) == []
    [entry] = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["moves"] == "tok_per_s" and entry["unit"] == "%"
    assert entry["better"] == "higher" and entry["workloads"] == [CELL]
    assert entry["source"] == ("program_counter" if name == CHUNKED
                               else "device_trace")
    if name == ROOFLINE:    # the layer the siblings' roofline shares name
        [other] = [m for m in manifest["per_layer"]
                   if m["name"] == "roofline_share.granite-summary"]
        assert entry["layer"] == other["layer"]
    else:
        assert "models/brumby.py" in entry["layer"]
    # nothing is attended in this cell, and its steps are counted by the
    # batch's own account: neither of these lists it
    for absent in ("attend_live_share.offline", "decode_step_ms"):
        [metric] = [m for m in manifest["per_layer"]
                    if m["name"] == absent]
        assert CELL not in metric["workloads"]
    [cell] = [c for c in manifest["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("brumby-14b-base", "longgen-batch", 1)


def test_the_kernels_share_is_its_bytes_over_its_self_time(config):
    read = _reader(KERNEL)
    # 126 steps x 10 layers, each 2 x 8 rows x 34.08 MB of state as stored
    per_row = 8 * 128 * 8320 * 4
    assert per_row == costs.layer_state_bytes_a_row(config) == 34078720
    moved = 126 * 10 * 2 * 8 * per_row
    at_peak = moved / 819e9
    ops = [["jit_decode_step/_fusion_f32_3_8_17408_", 1.2],
           [STEP_OP, at_peak / 0.75], ["jit_decode_step/_copy", 0.1]]
    assert read(_observed(config, ops)) == pytest.approx(75.0)
    halves = [[STEP_OP, at_peak],
              ["jit_decode_step_1/_retention_step_f32_", at_peak]]
    assert read(_observed(config, halves)) == pytest.approx(50.0)


@pytest.mark.parametrize("spoil", ["no-kernel", "no-trace", "no-steps"])
def test_without_the_operation_the_kernels_reader_reads_nothing(config,
                                                               spoil):
    """A program without the kernel (the jnp step: a backend without
    Mosaic, the parent), a run that was not traced, a traced window without
    a step: None, never an exception."""
    read = _reader(KERNEL)
    observed = _observed(config, [["jit_decode_step/_fusion_f32_", 3.0]])
    if spoil == "no-trace":
        observed["trace"] = None
    if spoil == "no-steps":
        observed = _observed(config, [[STEP_OP, 1.0]], generations=0)
    assert read(observed) is None


def test_the_roofline_share_is_what_the_calls_need_over_busy_seconds(config):
    read = _reader(ROOFLINE)
    step_s = costs.decode_step_bytes(config, 8) / 819e9
    prefill_s = max(costs.prefill_flops(config, 8, 1024) / 197e12,
                    costs.prefill_bytes(config, 8) / 819e9)
    # a step is bound by its bytes (16.7 ms), a prompt by its FLOPs
    assert costs.decode_step_flops(config, 8) / 197e12 < step_s
    assert costs.prefill_flops(config, 8, 1024) / 197e12 \
        > costs.prefill_bytes(config, 8) / 819e9
    assert 0.0166 < step_s < 0.0168
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
    counter's (every prompt position chunked), and nothing attended."""
    _, traced = run_cell(tiny_root, CELL, trace=True, seconds=0.5)
    assert traced["correct"] is True
    assert ROOFLINE not in traced["metrics"]
    assert KERNEL not in traced["metrics"]
    assert traced["metrics"][CHUNKED]["value"] == 100.0
    assert "attend_live_share.offline" not in traced["metrics"]
