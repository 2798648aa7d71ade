"""`attn_fused_share.vit`: the reader of the program's
`pipeedge_attn_core_blocks_total{path}` on a rendered registry, on what a
program without the counter leaves (the parent of PR 60: it reads nothing
and does not raise), off the chip, and in the traced line of the two ViT
cells' rehearsal."""
import os

import pytest

from benchmark import manifest as rules
from benchmark import run as bench_run
from pipeedge_tpu.telemetry import metrics

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "attn_fused_share.vit"
CELLS = ["vit-l.host-1stage", "vit-l.spmd-4stage"]
COUNTER = "pipeedge_attn_core_blocks_total"
ON_CHIP = {"peaks": {"bf16_flops_per_s": 197e12}}


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


def test_the_entry_names_the_cells_and_its_layer(source):
    manifest = rules.load(source)
    assert rules.problems(manifest, source) == []
    [entry] = [m for m in manifest["per_layer"] if m["name"] == NAME]
    [beside] = [m for m in manifest["per_layer"]
                if m["name"] == "mxu_share.vit"]
    assert entry["moves"] == "img_per_s" and entry["unit"] == "%"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == beside["layer"]
    assert entry["workloads"] == CELLS


@pytest.mark.parametrize("fused, einsum, share", [
    # the host cell's unrolled stage program: a sample a block, all fused
    (24, 0, 100.0),
    # a program whose rule kept every core on the einsums
    (0, 24, 0.0),
    (3, 1, 75.0),
])
def test_the_share_is_fused_over_all_traced(reader, registry, fused, einsum,
                                            share):
    blocks = registry.counter(COUNTER, "a rendered registry")
    blocks.inc(fused, path="fused")
    blocks.inc(einsum, path="einsum")
    assert reader(ON_CHIP) == pytest.approx(share)
    # off the chip the rule keeps every core on the einsums: not the
    # program a chip runs, so nothing is read
    assert reader({}) is None


@pytest.mark.parametrize("spoil", ["empty", "declared", "one-path"])
def test_without_the_counter_the_reader_reads_nothing(reader, registry,
                                                      spoil):
    """The parent's program has no such counter; one that traced no core
    has both paths at zero: None, never an exception, so that the line
    leaves the metric out."""
    if spoil == "declared":
        for path in ("fused", "einsum"):
            registry.counter(COUNTER, "").declare(path=path)
    if spoil == "one-path":
        registry.counter(COUNTER, "").inc(5, path="fused")
    assert reader(ON_CHIP) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_rehearsed_cells_leave_it_out(tiny_root, run_cell, cell):
    """On the CPU `layers._kernel_mode()` is None and the tiny twin's heads
    are 8 wide: the traced line carries no share, never one from the CPU."""
    _, traced = run_cell(tiny_root, cell, trace=True)
    assert NAME not in traced["metrics"]
