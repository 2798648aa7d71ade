"""`setup_weights_s`, `setup_programs_s`, `setup_compiled`: the readers of
the program's start-up counters on a hand-written scrape (the served cell's
`metrics_before`), on a text without the families (a program before them:
nothing is read and nothing raised), on the process's own registry, and on
what an in-process cell's rehearsal leaves in it. They read a chip run only
(`peaks` in `observed`, as `compiles_in_window.serve`): the CPU's seconds
are no number of a cell, so the rehearsal's traced line leaves them out."""
import os

import pytest

from benchmark import manifest as rules
from benchmark import run as bench_run
from pipeedge_tpu.telemetry import metrics

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAMES = ("setup_weights_s", "setup_programs_s", "setup_compiled")
LAYERS = {
    "setup_weights_s":
        "weight loader (`models/registry.py::module_shard_factory`)",
    "setup_programs_s": "program build (trace, lower, compile or cache read)",
    "setup_compiled": "program build (trace, lower, compile or cache read)"}

# what a server's first scrape after its warm-up may hold: a decode
# pipeline read from the cache but for one step program, eager operations
# beside it
SCRAPE = """\
# HELP pipeedge_startup_seconds_total seconds of set-up by phase
# TYPE pipeedge_startup_seconds_total counter
pipeedge_startup_seconds_total{phase="backend"} 7.25
pipeedge_startup_seconds_total{phase="programs"} 0.004
pipeedge_startup_seconds_total{phase="service"} 0.3
pipeedge_startup_seconds_total{phase="weights_place"} 1.75
pipeedge_startup_seconds_total{phase="weights_read"} 1.5
pipeedge_startup_bytes_total{phase="weights_read"} 709000000
pipeedge_jax_program_build_seconds_total{program="prefill",step="trace"} 0.5
pipeedge_jax_program_build_seconds_total{program="prefill",step="lower"} 0.25
pipeedge_jax_program_build_seconds_total{program="prefill",step="cache_read"} 0.125
pipeedge_jax_program_build_seconds_total{program="decode_step",step="trace"} 1
pipeedge_jax_program_build_seconds_total{program="decode_step",step="lower"} 0.5
pipeedge_jax_program_build_seconds_total{program="decode_step",step="cache_read"} 0.75
pipeedge_jax_program_build_seconds_total{program="decode_step",step="compile"} 2
pipeedge_jax_program_build_seconds_total{program="other",step="compile"} 40
pipeedge_jax_program_build_seconds_total{program="other",step="trace"} 3
pipeedge_jax_program_builds_total{program="prefill",step="trace"} 9
pipeedge_jax_program_builds_total{program="prefill",step="cache_read"} 9
pipeedge_jax_program_builds_total{program="prefill",step="compile"} 0
pipeedge_jax_program_builds_total{program="decode_step",step="cache_read"} 4
pipeedge_jax_program_builds_total{program="decode_step",step="compile"} 1
pipeedge_jax_program_builds_total{program="other",step="compile"} 57
pipeedge_jax_compiles_total 71
"""
EXPECTED = {"setup_weights_s": 3.25, "setup_programs_s": 5.125,
            "setup_compiled": 1.0}
BEFORE_THEM = """\
pipeedge_jax_compiles_total 71
pipeedge_jax_compile_seconds_total 12.5
pipeedge_span_seconds_total{cat="stage",name="exec0",stage="0"} 1.5
"""


CHIP = {"peaks": {"flops_per_s": 197e12}}     # what a chip run's line adds


def reader(name):
    return bench_run.load_reader(
        rules.reader_path(REPO, rules.load(REPO), name))


def test_the_entries_move_setup_s_in_every_accepted_cell(source):
    manifest = rules.load(source)
    assert rules.problems(manifest, source) == []
    accepted = [cell["name"] for cell in rules.load(REPO)["workloads"]]
    for name in NAMES:
        [entry] = [m for m in manifest["per_layer"] if m["name"] == name]
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert entry["source"] == "program_counter"
        assert entry["layer"] == LAYERS[name]
        assert entry["workloads"] == accepted


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_adds_up_the_served_cells_scrape(name):
    assert reader(name)(dict(CHIP, metrics_before=SCRAPE)) == EXPECTED[name]
    assert reader(name)({"metrics_before": SCRAPE}) is None     # no chip


@pytest.mark.parametrize("name", NAMES)
def test_without_the_families_a_reader_reads_nothing(name, monkeypatch):
    """The parent's server has the compile counters and no more; the
    parent's library has an empty registry: None, never an exception."""
    assert reader(name)(dict(CHIP, metrics_before=BEFORE_THEM)) is None
    monkeypatch.setattr(metrics, "REGISTRY", metrics.Registry())
    assert reader(name)(CHIP) is None


def test_in_process_a_reader_takes_the_programs_registry(monkeypatch):
    fresh = metrics.Registry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    fresh.counter("pipeedge_startup_seconds_total", "").inc(
        0.5, phase="weights_read")
    builds = fresh.counter("pipeedge_jax_program_builds_total", "")
    builds.declare(program="spmd_body", step="compile")
    assert reader("setup_weights_s")(CHIP) == 0.5
    assert reader("setup_compiled")(CHIP) == 0.0    # declared: a number
    assert reader("setup_programs_s")(CHIP) is None


def test_a_rehearsed_in_process_cell_leaves_all_three_to_read(tiny_root,
                                                             run_cell):
    """The traced line of the CPU rehearsal leaves them out; given what a
    chip run's line adds, the readers find three numbers in what the run
    left in the registry, and the weights' seconds lie inside the runner's
    own mark of the builder's call."""
    before = {name: reader(name)(CHIP) or 0.0 for name in NAMES}
    outcome, traced = run_cell(tiny_root, "gpt2-m.offline-batch", trace=True,
                               seconds=0.5)
    assert not set(NAMES) & set(traced["metrics"])
    gained = {name: reader(name)(dict(outcome.observed, **CHIP))
              - before[name] for name in NAMES}
    marks = outcome.notes["setup_marks"]
    assert 0.0 < gained["setup_weights_s"] \
        <= marks["pipeline_built"] - marks["weights_file"] + 0.002
    assert gained["setup_programs_s"] > 0.0
    assert gained["setup_compiled"] == int(gained["setup_compiled"]) >= 0
