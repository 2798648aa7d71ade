"""The committed manifest against the rules it is refused for, and the
rules themselves against manifests that break them."""
import copy
import os

import pytest

from benchmark import manifest as rules

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def committed():
    return rules.load(REPO)


def test_the_committed_manifest_breaks_no_rule(committed):
    assert rules.problems(committed, REPO) == []


def test_every_workloads_files_are_found_by_name(committed):
    for cell in committed["workloads"]:
        assert rules.traffic_path(REPO, committed, cell["traffic"])
        entry = rules.config_entry(committed, cell["config"])
        assert os.path.exists(os.path.join(REPO, entry["file"]))
    for metric in committed["per_layer"]:
        assert rules.reader_path(REPO, committed, metric["name"])


def test_every_layer_metric_moves_a_metric_its_cells_report(committed):
    for cell in committed["workloads"]:
        reported = {m["name"] for m in rules.metrics_of(
            committed, "end_to_end", cell["name"])}
        for metric in rules.metrics_of(committed, "per_layer", cell["name"]):
            assert metric["moves"] in reported, (cell["name"], metric["name"])


def test_at_most_one_cell_in_four_asks_for_four_chips(committed):
    cells = committed["workloads"]
    four = [cell for cell in cells if cell["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


def test_every_path_exists_and_the_command_stays_inside_them(committed):
    for base in committed["paths"]:
        assert os.path.isdir(os.path.join(REPO, base))
    assert committed["command"] == ["python3", "benchmark/run.py"]


def _break(manifest, how):
    broken = copy.deepcopy(manifest)
    how(broken)
    return broken


@pytest.mark.parametrize("how", [
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["workloads"][0].update(name=".hidden"),
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["end_to_end"][0].update(bound=0.2),
    lambda m: m["end_to_end"][0].update(source="program_span"),
    lambda m: m["per_layer"][0].update(moves="img_per_s"),  # a serving metric
    lambda m: m["per_layer"][0].update(name="no_such_reader"),
    lambda m: m["workloads"][0].update(chips=4),
    lambda m: m["workloads"][0].update(traffic="no-such-mix"),
    lambda m: m["workloads"][1].update(config="vit-large-patch16-224",
                                       traffic="host-1stage"),
    lambda m: m["workloads"][0].update(why="x" * 201),
    lambda m: m.update(run_seconds=52),
    lambda m: m.update(command=["python3", "../elsewhere/run.py"]),
    lambda m: m.update(command=["python3", "tools/serve.py"]),
    lambda m: m["configs"][0].update(file="README.md"),
    lambda m: m["per_layer"][0].update(why="a key the contract does not have"),
    lambda m: m["end_to_end"].pop(),        # setup_s
], ids=["space-in-name", "dot-first", "unit-with-spaces", "bound-over-0.1",
        "end-to-end-from-a-span", "moves-unreported-metric", "no-reader",
        "two-four-chip-cells", "no-traffic-file", "pair-twice", "long-why",
        "run-seconds-52", "command-leaves-repo", "command-outside-paths",
        "config-file-outside-paths", "extra-key", "no-setup_s"])
def test_a_broken_manifest_is_found_out(committed, how):
    assert rules.problems(_break(committed, how), REPO)
