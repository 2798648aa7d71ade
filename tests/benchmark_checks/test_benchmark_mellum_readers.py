"""The served mellum cell's three readers (`swa_live_share.mellum2-ide`,
`moe_tokens_per_expert.mellum2-ide`, `roofline_share.mellum2-ide`): each on
hand-built scrapes of the server's `/metrics` before and after a window, and
on what a program without those counters leaves (the parent of the PR that
added them), where each must read nothing and not raise; and
`benchmark/costs_mellum.py` against counts made by hand."""
import json
import os

import pytest

from benchmark import costs_mellum as costs
from benchmark import manifest as rules
from benchmark import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mellum2.ide-mixed"
NEW = ("swa_live_share.mellum2-ide", "moe_tokens_per_expert.mellum2-ide",
       "roofline_share.mellum2-ide")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
OLD_PROGRAM = 'pipeedge_decode_steps_total{executor="wave"} 7\n'

# what a window adds to each counter, and what stood there before it
BEFORE = {
    ('pipeedge_swa_positions_read_total', 'phase="decode"'): 1000,
    ('pipeedge_swa_positions_live_total', 'phase="decode"'): 900,
    ('pipeedge_moe_assignments_total', 'phase="decode"'): 500,
    ('pipeedge_moe_experts_touched_total', 'phase="decode"'): 500,
}
STEPS, ROWS, BELOW = 2000, 60000, 60000 * 3000
SPANS, POSITIONS, SPAN_BELOW = 1200, 600000, 600000 * 2000
GAIN = {
    ('pipeedge_swa_positions_read_total', 'phase="decode"'):
        6 * ROWS * 1025,
    ('pipeedge_swa_positions_live_total', 'phase="decode"'):
        6 * ROWS * 1024,
    ('pipeedge_swa_positions_read_total', 'phase="prefill"'):
        6 * POSITIONS * (1024 + 512),
    ('pipeedge_swa_positions_live_total', 'phase="prefill"'):
        6 * POSITIONS * 1024,
    ('pipeedge_moe_assignments_total', 'phase="decode"'): 8 * ROWS * 8,
    ('pipeedge_moe_experts_touched_total', 'phase="decode"'):
        8 * STEPS * 64,
    ('pipeedge_moe_experts_touched_total', 'phase="prefill"'):
        8 * SPANS * 64,
    ('pipeedge_moe_layer_calls_total', 'phase="decode"'): 8 * STEPS,
    ('pipeedge_decode_step_rows_total', 'kind="live"'): ROWS,
    ('pipeedge_attend_positions_total', 'kind="live",phase="decode"'): BELOW,
    ('pipeedge_attend_positions_total', 'kind="live",phase="prefill"'):
        SPAN_BELOW,
    ('pipeedge_prompt_spans_total', ''): SPANS,
    ('pipeedge_prompt_positions_total', ''): POSITIONS,
}


def _scrape(counts):
    return "".join(
        f"{name}{'{' + labels + '}' if labels else ''} {value}\n"
        for (name, labels), value in counts.items())


def _after():
    return {key: BEFORE.get(key, 0) + GAIN.get(key, 0)
            for key in set(BEFORE) | set(GAIN)}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mellum2-12b-a2.5b-instruct.json")) as file:
        return json.load(file)


@pytest.fixture(scope="module")
def readers():
    manifest = rules.load(REPO)
    return {name: bench_run.load_reader(rules.reader_path(REPO, manifest,
                                                          name))
            for name in NEW}


@pytest.fixture
def observed(config):
    return {"config": config, "peaks": PEAKS, "window_s": 60.0,
            "trace": {"busy_s": 0.9, "window_s": 1.0},
            "metrics_before": _scrape(BEFORE),
            "metrics_after": _scrape(_after())}


def test_the_manifest_names_the_three_readers_for_the_cell_alone():
    manifest = rules.load(REPO)
    mine = {metric["name"]: metric for metric in manifest["per_layer"]
            if metric["name"] in NEW}
    assert set(mine) == set(NEW)
    for metric in mine.values():
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "served_tok_per_s"


def test_the_window_share_is_the_windows_gain(readers, observed):
    live = 6 * ROWS * 1024 + 6 * POSITIONS * 1024
    read = 6 * ROWS * 1025 + 6 * POSITIONS * 1536
    assert readers["swa_live_share.mellum2-ide"](observed) \
        == pytest.approx(100.0 * live / read)


def test_tokens_an_expert_are_the_decode_phases_gain(readers, observed):
    # 30 live rows a step, 8 of 64 experts each, every expert touched
    assert readers["moe_tokens_per_expert.mellum2-ide"](observed) \
        == pytest.approx(30 * 8 / 64)


def test_the_roofline_share_is_needed_seconds_over_busy_seconds(
        readers, observed, config):
    steps_s = max(
        costs.steps_flops(config, ROWS, BELOW) / PEAKS["bf16_flops_per_s"],
        costs.steps_bytes(config, STEPS, ROWS, BELOW, 8 * STEPS * 64)
        / PEAKS["hbm_bytes_per_s"])
    spans_s = max(
        costs.spans_flops(config, SPANS, POSITIONS, SPAN_BELOW)
        / PEAKS["bf16_flops_per_s"],
        costs.spans_bytes(config, SPANS, POSITIONS, 8 * SPANS * 64)
        / PEAKS["hbm_bytes_per_s"])
    share = readers["roofline_share.mellum2-ide"](observed)
    assert share == pytest.approx(100.0 * (steps_s + spans_s) / (60.0 * 0.9))
    assert 0 < share < 100


@pytest.mark.parametrize("name, left", [
    (name, left) for name in NEW
    for left in ("no scrape", "an older program's scrape")]
    + [("roofline_share.mellum2-ide", "no trace and no peaks")])
def test_a_program_without_the_counters_reads_nothing(name, left, readers,
                                                      observed):
    if left == "no scrape":
        observed.pop("metrics_before"), observed.pop("metrics_after")
    elif left == "an older program's scrape":
        observed.update(metrics_before=OLD_PROGRAM,
                        metrics_after=OLD_PROGRAM)
    else:       # a CPU rehearsal: a counter's reader needs neither
        observed.pop("trace"), observed.pop("peaks")
    assert readers[name](observed) is None


def test_the_costs_count_what_the_shapes_say(config):
    d, head, width = 2304, 128, 896
    attention = 2 * d * 32 * head + 2 * d * 4 * head + 2 * head
    layer = attention + d * 64 + 2 * d + 64 * 3 * d * width
    assert costs.held_parameters(config) == 8 * layer + d + 2 * d * 98304
    assert costs.kv_bytes_a_position(config) == 4096
    # a slot of the stage-wide cache: 2 full layers to max_len, 6 rings
    assert costs.slot_bytes(config, 8704) == (2 * 8704 + 6 * 1024) * 4096
    # one live row of one step, nothing below it: its products, the head
    products = 2 * 8 * (attention - 2 * head + d * 64 + 8 * 3 * d * width)
    assert costs.steps_flops(config, 1, 0) == products + 2 * d * 98304
    # ... and every position below it once in the two full layers
    assert costs.steps_flops(config, 1, 5000) \
        - costs.steps_flops(config, 1, 0) == 2 * 4 * 32 * head * 5000
    # a step's bytes: what is outside the experts and one table once, the
    # touched experts, the row's keys and values (the rings no further than
    # the window)
    outside = 8 * (attention + d * 64 + 2 * d) + d
    assert costs.steps_bytes(config, 1, 1, 5000, 64) == 2 * (
        outside + d * 98304 + 64 * 3 * d * width) \
        + 4096 * (2 * 5000 + 6 * 1024)
    assert costs.spans_bytes(config, 1, 512, 8 * 64) == 2 * (
        outside + d * 98304 + 8 * 64 * 3 * d * width) + 512 * 8 * 4096
