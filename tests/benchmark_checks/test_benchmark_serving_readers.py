"""The serving cell's readers of the program's span digest, compile counter
and named idle time: each on a hand-built `observed`, and on what a program
without that span or counter leaves, where it must read nothing and not
raise."""
import os

import pytest

from benchmark import manifest as rules
from benchmark import run as bench_run
from benchmark import xplane

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "gpt2-m.chat-overload"
NEW = ("exec_dispatch_ms.serve", "exec_finish_ms.serve",
       "exec_wait_share.serve", "stream_readback_ms.serve",
       "compiles_in_window.serve", "idle_named_share.serve")

SECONDS = "pipeedge_span_seconds_total"
COUNT = "pipeedge_span_count_total"
# (cat, name, stage): (spans, seconds) at the scrape before the window, and
# what the window adds
BEFORE = {("stage", "exec0", "0"): (100, 0.5), ("exec", "wait0", "0"): (90, 9.0),
          ("exec", "pick", ""): (100, 0.25), ("serve", "write", ""): (100, 0.01)}
GAIN = {("stage", "exec0", "0"): (1000, 2.0), ("exec", "wait0", "0"): (1000, 0.25),
        ("exec", "pick", ""): (1000, 1.5), ("exec", "emit", ""): (1000, 0.125),
        ("exec", "reenter", ""): (970, 0.5), ("exec", "retire", ""): (30, 0.125),
        ("exec", "admit", ""): (30, 0.5), ("serve", "write", ""): (1000, 3.0)}
OLD_PROGRAM = "pipeedge_decode_steps_total{executor=\"wave\"} 7\n"


def _scrape(digest, compiles):
    lines = [f"pipeedge_jax_compiles_total {compiles}"]
    for (cat, name, stage), (spans, seconds) in digest.items():
        labels = f'{{cat="{cat}",name="{name}",stage="{stage}"}}'
        lines.append(f"{SECONDS}{labels} {seconds:.9f}")
        lines.append(f"{COUNT}{labels} {spans}")
    return "\n".join(lines) + "\n"


def _after():
    digest = dict(BEFORE)
    for key, (spans, seconds) in GAIN.items():
        had = digest.get(key, (0, 0.0))
        digest[key] = (had[0] + spans, had[1] + seconds)
    return digest


@pytest.fixture(scope="module")
def manifest():
    return rules.load(REPO)


@pytest.fixture(scope="module")
def readers(manifest):
    return {name: bench_run.load_reader(rules.reader_path(REPO, manifest, name))
            for name in NEW}


@pytest.fixture
def observed():
    ms = 1_000_000
    return {
        "peaks": {"hbm_bytes_per_s": 819e9},    # a run on a chip
        "metrics_before": _scrape(BEFORE, 41),
        "metrics_after": _scrape(_after(), 41),
        "spans_dropped": 0,
        "spans": [
            {"cat": "serve", "name": "readback", "t0": 0, "t1": 2 * ms},
            {"cat": "serve", "name": "readback", "t0": 5 * ms, "t1": 9 * ms},
            {"cat": "serve", "name": "write", "t0": 9 * ms, "t1": 10 * ms},
            {"cat": "stage", "name": "exec0", "t0": 0, "t1": 1 * ms}],
        "trace": {"window_s": 3.0, "busy_s": 1.0, "idle_gaps": [
            ["exec/pick", 0.9], ["stage/exec0", 0.6],
            [xplane.NO_HOST_EVENT, 0.3], ["PjitFunction(decode_step)", 0.1],
            [xplane.SHORT_GAPS, 0.1]]},
    }


def test_the_extended_manifest_breaks_no_rule(manifest):
    assert rules.problems(manifest, REPO) == []
    ours = {m["name"]: m for m in manifest["per_layer"] if m["name"] in NEW}
    assert list(ours) == list(NEW)      # appended, in the issue's order
    for metric in ours.values():
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "served_tok_per_s"
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == list(NEW)


@pytest.mark.parametrize("name, expected", [
    ("exec_dispatch_ms.serve", 2.0),            # 2.0 s over 1000 dispatches
    ("exec_finish_ms.serve", 2.25),             # 1.5 + .125 + .5 + .125 s
    ("exec_wait_share.serve", 5.0),             # 0.25 of 5.0 s of the worker
    ("stream_readback_ms.serve", 3.0),          # spans of 2 ms and 4 ms
    ("compiles_in_window.serve", 0.0),          # a number, not nothing
    ("idle_named_share.serve", 80.0),           # 0.4 of 2.0 idle s unnamed
])
def test_reader_on_a_hand_built_window(readers, observed, name, expected):
    assert readers[name](observed) == pytest.approx(expected)


def test_a_compile_inside_the_window_is_counted(readers, observed):
    observed["metrics_after"] = _scrape(_after(), 44)
    assert readers["compiles_in_window.serve"](observed) == 3.0


def _without_scrapes(observed):
    del observed["metrics_before"], observed["metrics_after"]


def _old_program(observed):
    observed["metrics_before"] = observed["metrics_after"] = OLD_PROGRAM


def _no_steps(observed):
    observed["metrics_after"] = observed["metrics_before"]


def _on_the_cpu(observed):
    del observed["peaks"]


def _dropped(observed):
    observed["spans_dropped"] = 12


def _no_readback_spans(observed):
    observed["spans"] = observed["spans"][2:]


def _untraced(observed):
    observed["trace"] = None


def _never_idle(observed):
    observed["trace"]["busy_s"] = observed["trace"]["window_s"]


@pytest.mark.parametrize("name, spoil", [
    ("exec_dispatch_ms.serve", _without_scrapes),
    ("exec_dispatch_ms.serve", _old_program),
    ("exec_dispatch_ms.serve", _no_steps),
    ("exec_dispatch_ms.serve", _on_the_cpu),
    ("exec_finish_ms.serve", _on_the_cpu),
    ("exec_wait_share.serve", _on_the_cpu),
    ("stream_readback_ms.serve", _on_the_cpu),
    ("compiles_in_window.serve", _on_the_cpu),
    ("exec_finish_ms.serve", _without_scrapes),
    ("exec_finish_ms.serve", _old_program),
    ("exec_wait_share.serve", _without_scrapes),
    ("exec_wait_share.serve", _old_program),
    ("stream_readback_ms.serve", _dropped),
    ("stream_readback_ms.serve", _no_readback_spans),
    ("compiles_in_window.serve", _without_scrapes),
    ("compiles_in_window.serve", _old_program),
    ("idle_named_share.serve", _untraced),
    ("idle_named_share.serve", _never_idle),
])
def test_reader_reads_nothing_where_there_is_nothing(readers, observed, name,
                                                     spoil):
    """A run without the scrapes or the trace, the parent's program, which
    has neither the digest families nor the compile counter, and the CPU
    rehearsal: None, never an exception, so that the line leaves the
    metric out."""
    spoil(observed)
    assert readers[name](observed) is None


def test_idle_named_share_reads_the_parents_trace_as_the_ledger_has_it(
        readers):
    """PR 23's ledger line of the cell: 2.0 idle seconds, 1.438 between
    host events and 0.182 beyond the longest 2000 gaps."""
    trace = {"window_s": 3.0, "busy_s": 1.0, "idle_gaps": [
        [xplane.NO_HOST_EVENT, 1.438], ["PjitFunction(run)", 0.2],
        [xplane.SHORT_GAPS, 0.182]]}
    assert readers["idle_named_share.serve"]({"trace": trace}) \
        == pytest.approx(19.0)


def test_readers_on_a_real_servers_scrapes_and_spans(tiny_root, run_cell,
                                                     readers):
    """The cell on the CPU at tiny size, through the server and the
    generator: its line leaves the chip-only numbers out, and the same
    readers, told the run was on a chip, find in the server's own scrapes
    and spans what they are written to read."""
    outcome, line = run_cell(tiny_root, CELL, trace=True, seconds=2.0)
    assert line["failed"] == 0 and not set(NEW) & set(line["metrics"])
    observed = dict(outcome.observed, peaks={})
    dispatch = readers["exec_dispatch_ms.serve"](observed)
    finish = readers["exec_finish_ms.serve"](observed)
    assert dispatch > 0 and finish > 0
    # two host phases of one token cannot take longer than the median gap
    # between a request's tokens took the client
    assert dispatch + finish < 4 * outcome.observed["summary"]["itl_p50_ms"]
    assert 0 <= readers["exec_wait_share.serve"](observed) <= 100
    assert readers["stream_readback_ms.serve"](observed) > 0
    assert readers["compiles_in_window.serve"](observed) >= 0
    # the executor's dispatches in the digest are the ring's, span for span
    steps = sum(1 for span in outcome.observed["spans"]
                if (span["cat"], span["name"]) == ("stage", "exec0"))
    gained = readers["exec_dispatch_ms.serve"].__globals__["gained"]
    assert gained(observed, COUNT, "stage", {"exec0"}) == steps
