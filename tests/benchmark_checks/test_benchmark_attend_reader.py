"""`attend_live_share.offline`: the reader of the program's
`pipeedge_attend_positions_total` on a rendered registry, on what a program
without the counter leaves (it reads nothing and does not raise), and in
the traced line of an offline cell's rehearsal."""
import os

import pytest

from benchmark import manifest as rules
from benchmark import run as bench_run
from pipeedge_tpu.telemetry import metrics

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "attend_live_share.offline"
COUNTER = "pipeedge_attend_positions_total"
OFFLINE = ("gpt2-m.offline-batch", "keye-vl2.long-batch",
           "kimi-k2.agent-batch", "qwen3-next.longdoc-batch")


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


def test_the_entry_names_the_offline_cells(source):
    manifest = rules.load(source)
    assert rules.problems(manifest, source) == []
    [entry] = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry["moves"] == "tok_per_s" and entry["unit"] == "%"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "decode pipeline"
    assert set(OFFLINE) <= set(entry["workloads"])


@pytest.mark.parametrize("counts, share", [
    # gpt2-m.offline-batch's steps a row: 255 at 512, and on the fine ladder
    ({("decode", "read"): 255 * 512, ("decode", "live"): 97665}, 74.8),
    ({("decode", "read"): 64 * (320 + 384 + 448) + 63 * 512,
      ("decode", "live"): 97665}, 92.2),
    # spans and steps are added up: keye's 30 spans after the first and 511
    # steps over 16384
    ({("prefill", "read"): 512 * 278528, ("prefill", "live"): 512 * 238080,
      ("decode", "read"): 511 * 16384, ("decode", "live"): 8240897}, 86.2),
    ({("prefill", "read"): 4096.0, ("prefill", "live"): 0.0}, 0.0),
])
def test_the_share_is_live_over_read(reader, registry, counts, share):
    counter = registry.counter(COUNTER, "a rendered registry")
    for (phase, kind), value in counts.items():
        counter.inc(value, phase=phase, kind=kind)
    registry.counter("pipeedge_moe_rows_computed_total", "beside it").inc(7)
    assert reader({}) == pytest.approx(share, abs=0.05)


@pytest.mark.parametrize("spoil", ["empty", "parent", "declared"])
def test_without_the_counter_the_reader_reads_nothing(reader, registry,
                                                      spoil):
    """The parent's program has no such counter, a program that attended
    nothing has it at zero: None, never an exception, so that the line
    leaves the metric out."""
    if spoil == "parent":
        registry.counter("pipeedge_moe_assignments_total", "").inc(
            5, phase="prefill")
    if spoil == "declared":
        registry.counter(COUNTER, "").declare(phase="decode", kind="read")
    assert reader({}) is None


def test_the_rehearsed_offline_cell_reports_the_share(tiny_root, run_cell):
    """On the CPU the number is a count, not a device metric: the traced
    line of the gpt2 offline cell carries it, between nothing live and
    everything."""
    _, traced = run_cell(tiny_root, OFFLINE[0], trace=True, seconds=0.5)
    assert traced["metrics"][NAME]["unit"] == "%"
    assert 0.0 < traced["metrics"][NAME]["value"] < 100.0
