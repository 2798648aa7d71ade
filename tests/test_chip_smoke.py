"""CPU rehearsal of chip_smoke.py: its control flow at tiny size.

The smoke itself needs a TPU and says so by failing. What can be checked
without one: that it does fail, at once and without an `"ok": true`; that a
phase which fails ends the run; and that every phase, driven through the same
CLIs at `pipeedge/test-tiny-*` size on the CPU, runs to its end and prints a
JSON line that parses. No number these runs print means anything.
"""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def tiny(chip_smoke):
    return dataclasses.replace(
        chip_smoke.Sizes(), vit="pipeedge/test-tiny-vit",
        two_stages="1,4,5,8", spmd_stages="1,4,5,8", batch=8, ubatch=4,
        decoder="pipeedge/test-tiny-gpt2", vocab=100, max_len=48,
        prompt_len=8, new_tokens=6, train_batch=2, train_ubatches=2,
        train_steps=3, edge_shape=(4, 5, 32), matmul_mkn=(16, 256, 128),
        fence_dim=128, fence_chain=2,
        attention_calls=((2, 9, 2, 64, "bfloat16"), (1, 5, 1, 128, "float32")))


def _host_devices(monkeypatch, n):
    """The children count their devices from the environment they inherit
    (the test process itself has conftest's eight)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       f"--xla_force_host_platform_device_count={n}")


def _phase_lines(capsys):
    return [json.loads(line)
            for line in capsys.readouterr().out.splitlines()]


def test_without_a_tpu_it_fails_at_once_and_prints_no_ok():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["phase"] == "probe" and last["ok"] is False
    assert "cpu" in last["error"]


def test_a_failing_phase_ends_the_run(chip_smoke, tiny, capsys):
    ran = []

    def phase_first(run):
        ran.append("first")
        return {}

    def phase_boom(run):
        raise chip_smoke.PhaseFailed("boom")

    def phase_never(run):
        ran.append("never")
        return {}

    with pytest.raises(chip_smoke.PhaseFailed, match="boom"):
        chip_smoke.run_phases((phase_first, phase_boom, phase_never),
                              tiny, "cpu", 1)
    assert ran == ["first"]
    lines = _phase_lines(capsys)
    assert [(ln["phase"], ln["ok"]) for ln in lines] == \
        [("first", True), ("boom", False)]


def test_one_chip_phases_at_tiny_size(chip_smoke, tiny, monkeypatch, capsys):
    _host_devices(monkeypatch, 1)
    device = chip_smoke.run_phases(chip_smoke.ONE_CHIP, tiny, "cpu", 1)
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    lines = _phase_lines(capsys)
    assert [ln["phase"] for ln in lines] == [
        "probe", "weights", "vit_one_stage", "vit_two_stages",
        "vit_two_stages_q8", "serve", "train"]
    assert all(ln["ok"] is True for ln in lines)
    by_phase = {ln["phase"]: ln for ln in lines}
    for name in ("vit_one_stage", "vit_two_stages", "vit_two_stages_q8",
                 "train"):
        assert by_phase[name]["device"]["platform"] == "cpu"
        assert by_phase[name]["cold_s"] > 0 and by_phase[name]["warm_s"] > 0
    assert by_phase["vit_two_stages"]["top1_agreement"] == 1.0
    assert by_phase["serve"]["stream_matches_plain"] is True
    assert by_phase["probe"]["dispatch_ms"] > 0
    assert len(by_phase["probe"]["kernel_checks"]["short_attention"][
        "gap_of_range"]) == 2
    # every bfloat16 input through both forms of the GeLU, counted
    gelu = by_phase["probe"]["kernel_checks"]["gelu"]
    assert gelu["shipped"]["counted"] == gelu["jax_nn_gelu"]["counted"] > 3e4
    assert gelu["shipped"]["past_an_ulp"] == 0
    # and two float32 samples: ISSUE 61's grid within its 4 ulp, with an
    # `exp` that is within one (a v5e's is 63 off: what the probe is for)
    assert gelu["exp_worst_ulp"] <= 1
    assert gelu["shipped"]["float32_worst_ulp"]["grid"] <= 4 \
        < gelu["jax_nn_gelu"]["float32_worst_ulp"]["grid"]
    assert gelu["shipped"]["float32_past_4_ulp"]["dense"] \
        < gelu["jax_nn_gelu"]["float32_past_4_ulp"]["dense"]
    # on the CPU `auto` is the XLA ops, by the backend's name: no kernel
    assert not any(by_phase["probe"]["kernel_in_program"].values())


def test_spmd_phases_at_tiny_size(chip_smoke, tiny, monkeypatch, capsys):
    """The --four-chips phases, with one stage on each of two virtual
    devices (the tiny model has two blocks)."""
    _host_devices(monkeypatch, 2)
    device = chip_smoke.run_phases(chip_smoke.FOUR_CHIPS, tiny, "cpu", 2)
    assert device["count"] == 2
    lines = _phase_lines(capsys)
    assert [ln["phase"] for ln in lines] == [
        "devices", "weights", "vit_one_stage", "vit_spmd_stages"]
    assert all(ln["ok"] is True for ln in lines)
    assert len(lines[-1]["device_memory"]) == 2
    assert lines[-1]["top1_agreement"] == 1.0
