"""The reduction from a trace to busy, idle, per-operation time and named
gaps: the arithmetic on hand-made intervals, and the reader on a small trace
recorded on a v5e chip (three jitted matmul chains with 2 ms host sleeps
between them, all under the window annotation; PR 23)."""
import os

import pytest

from benchmark import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "tiny_tpu.xplane.pb")
CHIP = "/device:TPU:0"


def test_busy_is_the_union_of_overlapping_operations():
    ops = {CHIP: [("a", 0, 100), ("b", 50, 150), ("c", 150, 200),
                  ("d", 400, 500)]}
    out = xplane.reduce(ops, [], window=(0, 1000))
    assert out["busy_s"] == pytest.approx(300e-9)      # [0, 200] and [400, 500]
    assert out["window_s"] == pytest.approx(1000e-9)
    assert xplane.idle_share(out) == pytest.approx(70.0)


def test_operations_are_clipped_to_the_window():
    ops = {CHIP: [("before", 0, 100), ("across", 150, 250), ("after", 900, 950)]}
    out = xplane.reduce(ops, [(xplane.WINDOW, 200, 800)])
    assert out["window_s"] == pytest.approx(600e-9)
    assert out["busy_s"] == pytest.approx(50e-9)
    assert [row[0] for row in out["device_ops"]] == ["across"]


def test_without_a_window_the_operations_span_is_taken():
    out = xplane.reduce({CHIP: [("a", 100, 200), ("b", 300, 400)]}, [])
    assert out["window_s"] == pytest.approx(300e-9)
    assert out["busy_s"] == pytest.approx(200e-9)


def test_no_operation_in_the_window_reduces_to_nothing():
    assert xplane.reduce({CHIP: [("a", 0, 10)]}, [], window=(100, 200)) is None
    assert xplane.reduce({}, []) is None
    assert xplane.idle_share(None) is None


def test_a_loop_is_not_charged_for_its_body():
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 20, 25), ("c", 40, 60),
           ("d", 150, 200)]
    assert xplane.self_times(ops) == pytest.approx(
        {"while": 60e-9, "a": 15e-9, "b": 5e-9, "c": 20e-9, "d": 50e-9})
    out = xplane.reduce({CHIP: ops}, [], window=(0, 300))
    assert out["busy_s"] == pytest.approx(150e-9)
    assert out["device_ops"][0] == ["while", pytest.approx(60e-9)]
    assert sum(row[1] for row in out["device_ops"]) \
        == pytest.approx(out["busy_s"])


def test_a_gap_is_named_by_the_innermost_host_event_over_half_of_it():
    ops = {CHIP: [("a", 0, 100), ("b", 300, 400), ("c", 1000, 1100)]}
    host = [(xplane.WINDOW, 0, 1200), ("round", 0, 1200),
            ("dispatch", 90, 310),          # covers the first gap whole
            ("read_back", 700, 1000)]       # covers half of the second
    out = xplane.reduce(ops, host)
    gaps = dict((name, seconds) for name, seconds in out["idle_gaps"])
    assert gaps["dispatch"] == pytest.approx(200e-9)
    assert gaps["read_back"] == pytest.approx(600e-9)
    assert gaps["round"] == pytest.approx(100e-9)       # the tail, 1100-1200
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_a_gap_no_host_event_covers_says_so():
    out = xplane.reduce({CHIP: [("a", 0, 10), ("b", 90, 100)]},
                        [("elsewhere", 200, 300)], window=(0, 100))
    assert out["idle_gaps"] == [[xplane.NO_HOST_EVENT, pytest.approx(80e-9)]]


def test_several_chips_are_averaged_and_kept_apart():
    ops = {"/device:TPU:0": [("a", 0, 100)], "/device:TPU:1": [("a", 0, 50)]}
    out = xplane.reduce(ops, [], window=(0, 100))
    assert out["busy_s"] == pytest.approx(75e-9)
    assert out["busy_s_per_chip"] == {
        "/device:TPU:0": pytest.approx(100e-9),
        "/device:TPU:1": pytest.approx(50e-9)}
    assert out["device_ops"] == [["a", pytest.approx(75e-9)]]


@pytest.mark.parametrize("text,short", [
    ("%fusion.84 = f32[1024]{0:T(1024)S(1)} fusion(f32[4096]{0} %x), "
     "kind=kLoop", "%fusion f32[1024]"),
    ("%copy-done = f32[1,50257]{1,0:T(1,128)S(1)} copy-done((f32[1",
     "%copy-done f32[1,50257]"),
    ("%fusion.7 = (f32[8,197]{1,0}, f32[8,197]{1,0}) fusion(%x)",
     "%fusion f32[8,197]"),
    ("jit_step(123)", "jit_step(123)"),
])
def test_an_operations_name_is_its_instruction_and_shape(text, short):
    assert xplane.short_name(text) == short


# -- the recorded trace -----------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return xplane.read(RECORDED)


def test_the_recorded_trace_has_one_device_and_the_window(recorded):
    device_ops, host_events = recorded
    assert list(device_ops) == [CHIP]
    assert len(device_ops[CHIP]) >= 3 * 4      # three chains of 4 fused matmuls
    assert sum(1 for event in host_events if event[0] == xplane.WINDOW) == 1
    assert all(name.startswith("jit_chain/") for name, _, _ in device_ops[CHIP])


def test_the_recorded_trace_reduces_to_three_busy_spells(recorded):
    device_ops, host_events = recorded
    first = min(start for _, start, _ in device_ops[CHIP])
    last = max(end for _, _, end in device_ops[CHIP])
    out = xplane.reduce(device_ops, host_events, window=(first, last))
    # three chains of about 9 us, two idle spells of over 2 ms between them
    assert 0.004 < out["window_s"] < 0.1
    assert out["busy_s"] == pytest.approx(
        sum(xplane.self_times(device_ops[CHIP]).values()), rel=1e-6)
    assert 20e-6 < out["busy_s"] < 60e-6
    assert out["device_ops"][0][0] \
        == "jit_chain/%convolution_tanh_fusion bf16[512,512]"
    gaps = dict((name, seconds) for name, seconds in out["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    assert 99 < xplane.idle_share(out) < 100


def test_the_recorded_traces_window_is_the_hosts_annotation(recorded):
    """The device's events sit about a millisecond before the host events
    that dispatched them (the two timelines of one trace are aligned no
    better than that), so the first chain falls before the window the host
    annotated and the sleeps are named a millisecond late. Over a window
    of seconds that is nothing; a gap of under a few milliseconds is named
    by what the host did a millisecond after it."""
    out = xplane.reduce(*recorded)
    assert 0.006 < out["window_s"] < 0.1       # three 2 ms sleeps and more
    whole = sum(xplane.self_times(recorded[0][CHIP]).values())
    assert out["busy_s"] == pytest.approx(2 / 3 * whole, rel=0.05)
    gaps = dict((name, seconds) for name, seconds in out["idle_gaps"])
    assert gaps["tiny.sleep"] >= 0.006
