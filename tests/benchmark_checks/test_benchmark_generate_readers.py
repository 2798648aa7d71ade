"""The four `*.generate` readers of the decode pipeline's batch account
(`step_ms`, `prompt_tok_per_s`, `host_busy_share`, `stall_share`): on a
rendered registry of hand-made counters, on what a program without the
families leaves (the parent of PR 49: each reads nothing and does not
raise), in the manifest, and in the traced line of a cell's rehearsal."""
import os

import pytest

from benchmark import manifest as rules
from benchmark import run as bench_run
from pipeedge_tpu.telemetry import metrics

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAMES = {"step_ms.generate": ("ms", "lower"),
         "prompt_tok_per_s.generate": ("tokens/s", "higher"),
         "host_busy_share.generate": ("%", "lower"),
         "stall_share.generate": ("%", "lower")}
CELLS = ["gpt2-m.offline-batch", "keye-vl2.long-batch", "kimi-k2.agent-batch",
         "qwen3-next.longdoc-batch", "lfm2.extract-batch",
         "laguna-xs2.repo-batch", "minicpm-sala.longctx-batch",
         "nemotron3-super.reason-batch"]
SECONDS = "pipeedge_generate_seconds_total"
POSITIONS = "pipeedge_generate_positions_total"
STEPS = "pipeedge_generate_steps_total"
CPU = "pipeedge_generate_host_cpu_seconds_total"
STALLS = "pipeedge_generate_stall_seconds_total"


def _reader(name):
    return bench_run.load_reader(
        rules.reader_path(REPO, rules.load(REPO), name))


@pytest.fixture
def registry(monkeypatch):
    """A registry of the test's own in the program's place."""
    fresh = metrics.Registry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


def _four_batches(registry):
    """Four steady batches of gpt2's cell as the account would count them:
    32 rows x 256 in + 256 out, 0.08 s of prompt and 0.96 s of steps each,
    0.15 s of the thread's CPU, one stall of 0.104 s on the device's side."""
    seconds = registry.counter(SECONDS, "hand-made")
    seconds.inc(4 * 0.08, phase="prompt")
    seconds.inc(4 * 0.96, phase="decode")
    registry.counter(POSITIONS, "").inc(4 * 32 * 256, phase="prompt")
    registry.counter(STEPS, "").inc(4 * 255)
    registry.counter(CPU, "").inc(4 * 0.15)
    stalls = registry.counter(STALLS, "")
    stalls.inc(0.104, side="device")
    stalls.inc(0.0, side="host")
    # beside them, what no reader may take for its own
    registry.counter(POSITIONS, "").inc(4 * 32 * 255, phase="decode")
    registry.counter(SECONDS, "").inc(9.0, phase="wait")


@pytest.mark.parametrize("name, value", [
    ("step_ms.generate", 1e3 * 0.96 / 255),
    ("prompt_tok_per_s.generate", 32 * 256 / 0.08),
    ("host_busy_share.generate", 100 * 0.15 / 1.04),
    ("stall_share.generate", 100 * 0.104 / (4 * 1.04)),
])
def test_a_reader_on_hand_made_counters(registry, name, value):
    _four_batches(registry)
    assert _reader(name)({}) == pytest.approx(value)


@pytest.mark.parametrize("name", sorted(NAMES))
@pytest.mark.parametrize("spoil", ["empty", "no-batch", SECONDS, "one-phase"])
def test_a_reader_reads_nothing_where_its_family_is_missing(registry, name,
                                                            spoil):
    """The parent's registry has none of the families; a process that has
    run no steady batch has them all at zero; `SECONDS` missing, or one of
    its phases, leaves every one of the four without its divisor: None,
    never an exception, so that the line leaves the metric out."""
    if spoil == "no-batch":
        for phase in ("prompt", "decode"):
            registry.counter(SECONDS, "").declare(phase=phase)
        registry.counter(POSITIONS, "").declare(phase="prompt")
        for family in (STEPS, CPU):
            registry.counter(family, "").declare()
        for side in ("host", "device"):
            registry.counter(STALLS, "").declare(side=side)
    if spoil in (SECONDS, "one-phase"):
        _four_batches(registry)
        kept = registry.counter(SECONDS, "")
        with kept._lock:
            for key in list(kept._values):
                if spoil == SECONDS or dict(key)["phase"] == "prompt":
                    del kept._values[key]
    read = _reader(name)({})
    if spoil == "one-phase" and name == "step_ms.generate":
        assert read == pytest.approx(1e3 * 0.96 / 255)  # decode alone
    else:
        assert read is None


def test_the_entries_name_the_decode_pipeline_and_its_eight_cells(source):
    manifest = rules.load(source)
    assert rules.problems(manifest, source) == []
    [beside] = [m for m in manifest["per_layer"]
                if m["name"] == "decode_step_ms"]
    for name, (unit, better) in NAMES.items():
        [entry] = [m for m in manifest["per_layer"] if m["name"] == name]
        assert (entry["unit"], entry["better"]) == (unit, better)
        assert entry["source"] == "program_counter"
        assert entry["moves"] == "tok_per_s"
        assert entry["layer"] == beside["layer"] == "decode pipeline"
        assert entry["workloads"][:len(CELLS)] == CELLS


def test_the_manifest_differs_from_its_parent_by_appended_entries_only():
    """`BENCHMARK.json` is its parent's (git's HEAD where there is one)
    with entries appended to `per_layer`; where there is no git, the four
    entries are its last."""
    import json
    import subprocess
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf8") as file:
        mine = json.load(file)
    assert [m["name"] for m in mine["per_layer"]
            if m["name"] in NAMES] == list(NAMES)
    try:
        parent = json.loads(subprocess.run(
            ["git", "show", "HEAD:BENCHMARK.json"], cwd=REPO, check=True,
            capture_output=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        return
    import later_pr
    assert later_pr.only_appended(parent, mine)


@pytest.mark.parametrize("cell", ["gpt2-m.offline-batch",
                                  "laguna-xs2.repo-batch"])
def test_the_rehearsed_cell_reports_all_four(tiny_root, run_cell, cell):
    """A whole prompt and a spanned one: the window's batches are steady
    (the warm batch built every program), the traced generations are not
    counted, and each reader finds its counters."""
    steps = metrics.REGISTRY.counter(STEPS, "")
    before = steps.value()
    outcome, traced = run_cell(tiny_root, cell, trace=True, seconds=0.5)
    observed = outcome.observed
    assert outcome.notes["batches"] >= 1
    # the window's batches and, where its length is the window's (it then
    # builds no program), the one short generation before the trace; none
    # of the traced ones
    assert steps.value() - before - observed["decode_steps"] in (
        0, observed["trace_new_tokens"] - 1)
    for name, (unit, _) in NAMES.items():
        assert traced["metrics"][name]["unit"] == unit
        assert traced["metrics"][name]["value"] >= 0
    assert traced["metrics"]["step_ms.generate"]["value"] > 0
    assert traced["metrics"]["prompt_tok_per_s.generate"]["value"] > 0
    assert traced["metrics"]["stall_share.generate"]["value"] <= 100
