"""`ssm_fused_share.nemotron-reason`: the reader of the program's
`pipeedge_ssm_steps_fused_total` over `pipeedge_ssm_positions_stepped_total`
on a rendered registry, on what a program without the counter leaves (the
parent of PR 48: it reads nothing and does not raise), and in the traced
line of the cell's rehearsal."""
import os

import pytest

from benchmark import manifest as rules
from benchmark import run as bench_run
from pipeedge_tpu.telemetry import metrics

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "ssm_fused_share.nemotron-reason"
CELL = "nemotron3-super.reason-batch"
FUSED = "pipeedge_ssm_steps_fused_total"
STEPPED = "pipeedge_ssm_positions_stepped_total"


@pytest.fixture
def reader():
    return bench_run.load_reader(
        rules.reader_path(REPO, rules.load(REPO), NAME))


@pytest.fixture
def registry(monkeypatch):
    """A registry of the test's own in the program's place."""
    fresh = metrics.Registry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


def test_the_entry_names_the_cell_and_its_layer(source):
    manifest = rules.load(source)
    assert rules.problems(manifest, source) == []
    [entry] = [m for m in manifest["per_layer"] if m["name"] == NAME]
    [beside] = [m for m in manifest["per_layer"]
                if m["name"] == "ssm_chunked_share.nemotron-reason"]
    assert entry["moves"] == "tok_per_s" and entry["unit"] == "%"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == beside["layer"]
    assert entry["workloads"] == [CELL]


@pytest.mark.parametrize("fused, stepped, share", [
    # the cell's batch: 128 rows x 511 steps x 5 Mamba-2 layers, all fused
    (128 * 511 * 5, 128 * 511 * 5, 100.0),
    # a backend without Mosaic: the counter is there and stays at zero
    (0, 128 * 511 * 5, 0.0),
    (3, 12, 25.0),
])
def test_the_share_is_fused_over_stepped(reader, registry, fused, stepped,
                                         share):
    registry.counter(FUSED, "a rendered registry").inc(fused, phase="decode")
    registry.counter(STEPPED, "beside it").inc(stepped, phase="decode")
    # a prefill steps no position through the kernel and is not read
    registry.counter(STEPPED, "").inc(7, phase="prefill")
    registry.counter(FUSED, "").inc(0, phase="prefill")
    assert reader({}) == pytest.approx(share)


@pytest.mark.parametrize("spoil", ["empty", "parent", "no-step"])
def test_without_either_counter_the_reader_reads_nothing(reader, registry,
                                                         spoil):
    """The parent's program counts stepped positions and has no
    `ssm_steps_fused`; a program that stepped nothing has both at zero:
    None, never an exception, so that the line leaves the metric out."""
    if spoil == "parent":
        registry.counter(STEPPED, "").inc(128 * 511 * 5, phase="decode")
    if spoil == "no-step":
        registry.counter(STEPPED, "").inc(0, phase="decode")
        registry.counter(FUSED, "").inc(0, phase="decode")
    assert reader({}) is None


def test_the_rehearsed_cell_reports_the_share(tiny_root, run_cell):
    """On the CPU no step takes the kernel (`nemotron_h._kernel_mode` is
    None there) and the tiny twin's leaf is not placed: the traced line
    carries the metric at 0, a count and not a device metric."""
    _, traced = run_cell(tiny_root, CELL, trace=True, seconds=0.5)
    assert traced["metrics"][NAME]["unit"] == "%"
    assert traced["metrics"][NAME]["value"] == 0.0
