"""The account `DecodePipeline.generate` keeps of its batch
(`pipeedge_tpu/telemetry/generate_account.py`): what it counts and what it
keeps out of its counters, the stall rule on synthetic timelines, the spans
in a ring, and the families' declared label matrices."""
import logging
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu import telemetry
from pipeedge_tpu.analysis import lint
from pipeedge_tpu.parallel import decode
from pipeedge_tpu.telemetry import generate_account as ga
from pipeedge_tpu.telemetry import metrics
from pipeedge_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, PROMPT, NEW = 2, 20, 12
FAMILIES = {
    "pipeedge_generate_seconds_total": [{"phase": "prompt"},
                                        {"phase": "decode"}],
    "pipeedge_generate_positions_total": [{"phase": "prompt"}],
    "pipeedge_generate_steps_total": [{}],
    "pipeedge_generate_host_cpu_seconds_total": [{}],
    "pipeedge_generate_stall_seconds_total": [{"side": "host"},
                                              {"side": "device"}],
}


def _pipe(model="pipeedge/test-tiny-gpt2"):
    return decode.build_decode_pipeline(model, None, max_len=48,
                                        dtype=jnp.float32)


def _ids(seed=0, rows=ROWS):
    return np.random.default_rng(seed).integers(0, 50, size=(rows, PROMPT))


@pytest.fixture(scope="module")
def pipe():
    built = _pipe()
    np.asarray(built.generate(_ids(), NEW))     # every program built
    return built


def _counters():
    """Every sample of the five families, by (family, labels)."""
    return {(name, tuple(sorted(labels.items()))):
            metrics.REGISTRY.counter(name, "").value(**labels)
            for name, matrix in FAMILIES.items() for labels in matrix}


def _gained(before):
    return {key: value - before[key] for key, value in _counters().items()
            if value != before[key]}


# -- what a batch counts -------------------------------------------------

def test_two_warm_batches_add_up_to_the_clock_around_them(pipe):
    before = _counters()
    start = time.monotonic()
    for seed in (1, 2):
        np.asarray(pipe.generate(_ids(seed), NEW))
    around = time.monotonic() - start
    gained = _gained(before)
    seconds = sum(value for (name, _), value in gained.items()
                  if name == "pipeedge_generate_seconds_total")
    # within a dispatch: what the clock around them holds besides is the
    # prompts' draw, the read-back and the accounts' settling
    assert seconds <= around
    assert around - seconds < 0.05
    assert gained[("pipeedge_generate_positions_total",
                   (("phase", "prompt"),))] == 2 * ROWS * PROMPT
    assert gained[("pipeedge_generate_steps_total", ())] == 2 * (NEW - 1)
    assert 0 < gained[("pipeedge_generate_host_cpu_seconds_total", ())] \
        <= around + 0.01
    kept = list(pipe.batch_accounts)[-2:]
    assert [a["kind"] for a in kept] == ["steady", "steady"]
    # prompt and decode seconds are the whole batch, its tail included
    assert seconds == pytest.approx(sum(a["batch_s"] for a in kept))
    assert 0 < sum(sum(a["host_s"].values()) for a in kept) <= seconds


def test_the_account_of_a_batch_is_a_plain_dict_of_its_marks(pipe):
    np.asarray(pipe.generate(_ids(3), NEW))
    account = pipe.batch_accounts[-1]
    import json
    assert json.loads(json.dumps(account)) == account
    assert (account["rows"], account["prompt_positions"],
            account["steps"]) == (ROWS, PROMPT, NEW - 1)
    marks = account["marks"]
    # the first token parts prompt from steps; the last token is the last
    # mark, and what follows it to the batch's end the tail
    assert [m[:2] for m in marks if m[0] == "prompt"] == [["prompt", 1]]
    assert marks[-2][:2] == ["decode", NEW - 1]
    assert marks[-1][:2] == ["tail", 1]
    times = [m[2] for m in marks]
    assert times == sorted(times)
    assert times[-1] == pytest.approx(account["batch_s"], abs=1e-5)
    assert account["prompt_s"] + account["decode_s"] == pytest.approx(
        account["batch_s"])
    assert account["tail_s"] == pytest.approx(times[-1] - times[-2],
                                              abs=1e-5)
    assert 0 < account["tail_s"] < account["decode_s"]
    assert set(account["host_s"]) == set(ga.HOST_PHASES)
    assert account["dispatched_s"] <= account["batch_s"]
    assert all(m[3] >= 0 for m in marks)             # the host's lead
    # the thread's involuntary switches, an interval each
    assert sum(m[4] for m in marks) == account["switches"] >= 0
    assert account["longest"]["phase"] in ga.UNITS
    assert ga.account_line(account).startswith("account: steady batch ")


def test_a_batch_that_builds_a_program_adds_no_seconds():
    before = _counters()
    fresh = _pipe()
    np.asarray(fresh.generate(_ids(), NEW))
    assert _gained(before) == {}
    assert fresh.batch_accounts[-1]["kind"] == "building"
    # a new width of the same pipeline builds again
    np.asarray(fresh.generate(_ids(), NEW))
    assert fresh.batch_accounts[-1]["kind"] == "steady"
    np.asarray(fresh.generate(_ids(rows=3), NEW))
    assert fresh.batch_accounts[-1]["kind"] == "building"
    # and so does another count of tokens: the result's one program
    before = _counters()
    np.asarray(fresh.generate(_ids(), NEW - 2))
    assert fresh.batch_accounts[-1]["kind"] == "building"
    assert _gained(before) == {}


def test_a_batch_under_a_profiler_session_adds_no_seconds(pipe, tmp_path):
    before = _counters()
    with tracing.trace(str(tmp_path)):
        np.asarray(pipe.generate(_ids(4), NEW))
    assert _gained(before) == {}
    account = pipe.batch_accounts[-1]
    assert account["kind"] == "traced" and account["decode_s"] > 0


def test_the_spans_of_a_traced_batch_lie_inside_generate_batch(pipe,
                                                                tmp_path):
    from jax.profiler import ProfileData
    import glob
    with tracing.trace(str(tmp_path)):
        np.asarray(pipe.generate(_ids(5), NEW))
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for e in line.events
              if e.name.startswith("generate/")]
    [(_, b0, b1)] = [e for e in events if e[0] == "generate/batch"]
    inside = {name for name, t0, t1 in events if b0 <= t0 and t1 <= b1}
    assert inside >= {"generate/" + phase for phase in
                      ("alloc", "prefill", "step", "pick", "finish")}
    assert all(b0 <= t0 and t1 <= b1 for _, t0, t1 in events)


def test_the_result_is_one_program_dispatched_before_the_wait(pipe,
                                                              tmp_path):
    """After the last pick the thread dispatches `join_tokens` and nothing
    else: no column a token, so the device is not left idle under a
    dispatch a token (PERF.md section 6, PR 49)."""
    from jax.profiler import ProfileData
    import glob
    with tracing.trace(str(tmp_path)):
        out = np.asarray(pipe.generate(_ids(5), NEW))
    assert out.shape == (ROWS, PROMPT + NEW)
    assert (out[:, :PROMPT] == _ids(5)).all()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    [line] = [events for plane in ProfileData.from_file(path).planes
              if not plane.name.startswith("/device:")
              for events in (list(line.events) for line in plane.lines)
              if any(e.name == "generate/batch" for e in events)]
    [batch] = [e for e in line if e.name == "generate/batch"]
    picked = max(e.start_ns + e.duration_ns for e in line
                 if e.name == "generate/pick")
    after = [e for e in line if picked <= e.start_ns
             < batch.start_ns + batch.duration_ns
             and e.name.startswith("PjitFunction(")]
    # (the profiler shows a call with `*args` as two events, one inside
    # the other)
    assert {e.name for e in after} == {"PjitFunction(join_tokens)"}
    # (the first `finish` is the copies of the counts, after the prompt)
    finish = [e for e in line if e.name == "generate/finish"][-1]
    assert all(finish.start_ns <= e.start_ns and e.start_ns + e.duration_ns
               <= finish.start_ns + finish.duration_ns for e in after)
    assert all(finish.start_ns < e.start_ns for e in line
               if e.name == "generate/wait")


def test_a_slow_tail_is_a_stall_of_the_batch(monkeypatch):
    """What follows the last token (the counts' read-back here) is the
    batch's last interval, held to the steps: it is in `decode` seconds and
    a long one is a stall like any other."""
    built = _pipe("pipeedge/test-tiny-keye")    # counts on the device
    for seed in (0, 1):
        np.asarray(built.generate(_ids(seed), NEW))
    usual = built.batch_accounts[-1]
    count = built._count

    def slow(*args):
        time.sleep(0.2)
        count(*args)
    monkeypatch.setattr(built, "_count", slow)
    before = _counters()
    np.asarray(built.generate(_ids(2), NEW))
    account = built.batch_accounts[-1]
    assert account["tail_s"] >= 0.2 > usual["tail_s"]
    assert account["decode_s"] >= account["tail_s"]
    stall = max(account["stalls"], key=lambda s: s["excess"])
    assert stall["phase"] == "tail" and 0.15 < stall["excess"] < 0.4
    gained = _gained(before)
    assert gained[("pipeedge_generate_seconds_total",
                   (("phase", "decode"),))] == pytest.approx(
                       account["decode_s"])
    assert sum(value for (name, _), value in gained.items()
               if name == "pipeedge_generate_stall_seconds_total") \
        >= stall["excess"]
    assert "in the tail" in ga.stall_line(account)


def test_a_batch_that_raises_settles_nothing(pipe):
    before = _counters()
    kept = len(pipe.batch_accounts)

    def fail(step, token):
        raise RuntimeError("the caller's own")
    with pytest.raises(RuntimeError):
        pipe.generate(_ids(6), NEW, step_callback=fail)
    assert _gained(before) == {} and len(pipe.batch_accounts) == kept


def test_no_tokens_asked_is_no_batch(pipe):
    before = _counters()
    assert pipe.generate(_ids(7), 0).shape == (ROWS, PROMPT)
    assert _gained(before) == {}


def test_the_pipeline_keeps_the_last_64_accounts(pipe):
    assert pipe.batch_accounts.maxlen == ga.ACCOUNTS_KEPT == 64


@pytest.mark.parametrize("model, spans", [
    ("pipeedge/test-tiny-gpt2", 1),         # the prompt is one program
    ("pipeedge/test-tiny-laguna", 5),       # spans of 4 over 20 positions
])
def test_a_spanned_prompt_is_marked_span_by_span(model, spans):
    built = _pipe(model)
    np.asarray(built.generate(_ids(), NEW))
    np.asarray(built.generate(_ids(1), NEW))
    account = built.batch_accounts[-1]
    prompt = [m[1] for m in account["marks"] if m[0] == "prompt"]
    # marks found ready at one look are merged; the first token's stays
    assert prompt[-1] == spans and prompt == sorted(set(prompt))
    assert all(1 <= at <= spans for at in prompt)


def test_a_prompt_in_chunks_is_one_prompt_of_the_batch(pipe):
    for _ in range(2):
        np.asarray(pipe.generate(_ids(rows=4), NEW, prefill_ubatch=2))
    account = pipe.batch_accounts[-1]
    assert account["kind"] == "steady" and account["rows"] == 4
    assert [m[:2] for m in account["marks"] if m[0] == "prompt"] == [
        ["prompt", 1]]


def test_a_prefix_suffix_is_the_batchs_prompt(pipe):
    handle = pipe.precompute_prefix(_ids(8)[0, :8])
    for _ in range(2):
        np.asarray(pipe.generate(_ids(8)[:, 8:], NEW, prefix=handle))
    account = pipe.batch_accounts[-1]
    assert account["kind"] == "steady"
    assert account["prompt_positions"] == PROMPT - 8
    assert account["host_s"]["alloc"] > 0 and account["host_s"]["prefill"] > 0


def test_a_prompt_without_an_account_keeps_its_spans(pipe):
    """The beam search prefills through the same `_prefill` and keeps no
    account: `generate/alloc` and `generate/prefill` are the plain probe's
    spans there, in no `generate/batch`."""
    kept = len(pipe.batch_accounts)
    rec = telemetry.configure()
    try:
        pipe.generate_beam(_ids(10), 3, beams=2)
    finally:
        telemetry.disable()
    names = {s["name"] for s in rec.snapshot() if s["cat"] == "generate"}
    assert {"alloc", "prefill"} <= names and "batch" not in names
    assert len(pipe.batch_accounts) == kept
    assert ga.NO_ACCOUNT.span("alloc") is telemetry.span("generate", "alloc")


def test_a_sunk_span_hands_its_sink_the_rings_own_stamps():
    """One probe: what the ring records and what the sink adds up are the
    same two clock readings, and without a ring the sink still gets them."""
    got = []
    rec = telemetry.configure()
    try:
        with telemetry.sunk_span("generate", "alloc", lambda *a: got.append(a)):
            time.sleep(0.001)
    finally:
        telemetry.disable()
    [span] = rec.snapshot()
    assert got == [("alloc", span["t0"], span["t1"])]
    assert span["t1"] - span["t0"] >= 1_000_000
    with telemetry.sunk_span("generate", "alloc", lambda *a: got.append(a)):
        pass
    assert len(got) == 2 and got[1][0] == "alloc"


# -- the spans in a ring -------------------------------------------------

def test_generate_batch_covers_every_phase_in_a_ring():
    built = _pipe("pipeedge/test-tiny-keye")    # counts on the device too
    np.asarray(built.generate(_ids(), NEW))
    rec = telemetry.configure()
    try:
        np.asarray(built.generate(_ids(1), NEW))
    finally:
        telemetry.disable()
    spans = [s for s in rec.snapshot() if s["cat"] == "generate"]
    [batch] = [s for s in spans if s["name"] == "batch"]
    phases = [s for s in spans if s["name"] != "batch"]
    assert {s["name"] for s in phases} == set(ga.HOST_PHASES)
    assert all(batch["t0"] <= s["t0"] and s["t1"] <= batch["t1"]
               for s in phases)
    # the phases are the host's calls: they do not overlap
    ordered = sorted(phases, key=lambda s: s["t0"])
    assert all(a["t1"] <= b["t0"] for a, b in zip(ordered, ordered[1:]))
    # one step and one pick a token, one pick more for the first
    names = [s["name"] for s in phases]
    assert names.count("pick") == NEW and names.count("step") == NEW - 1
    account = built.batch_accounts[-1]
    for phase in ga.HOST_PHASES:
        ring = sum(s["t1"] - s["t0"] for s in phases if s["name"] == phase)
        assert account["host_s"][phase] == pytest.approx(ring / 1e9)


def test_without_a_ring_or_a_session_the_account_is_kept_all_the_same(pipe):
    assert telemetry.recorder() is None
    kept = len(pipe.batch_accounts)
    np.asarray(pipe.generate(_ids(9), NEW))
    assert len(pipe.batch_accounts) == min(kept + 1, ga.ACCOUNTS_KEPT)


# -- the rule, on synthetic timelines -------------------------------------

def _timeline(rates, between=0.1, phase="decode", units=16, slow=None,
              side=None):
    """Marks every `units` units; interval i takes rates[i] seconds a
    unit, a unit being a call and `between` of it the host's own time
    between calls. Interval `slow` takes 2 s more: inside one call
    (`side="device"`) or between two (`side="host"`)."""
    calls, marks, now, at = [], [], 0.0, 0
    for i, rate in enumerate(rates):
        for unit in range(units):
            extra = 2.0 if i == slow and unit == units // 2 else 0.0
            gap = rate * between + (extra if side == "host" else 0.0)
            busy = rate * (1 - between) + (extra if side == "device" else 0.0)
            calls.append((now + gap, now + gap + busy))
            now += gap + busy
        at += units
        marks.append((phase, at, now))
    return calls, marks


@pytest.mark.parametrize("side", ["host", "device"])
@pytest.mark.parametrize("slow", [0, 3, 7])
def test_a_long_interval_is_a_stall_on_the_side_the_host_spent_it(side, slow):
    calls, marks = _timeline([0.004] * 8, slow=slow, side=side)
    rows = ga.intervals(0.0, calls, marks)
    assert [row["units"] for row in rows] == [16] * 8
    [stall] = ga.find_stalls(rows)
    assert (stall["index"], stall["side"]) == (slow, side)
    assert stall["excess"] == pytest.approx(2.0)
    assert stall["at"] == 16 * (slow + 1) and stall["phase"] == "decode"
    assert stall["between"] == pytest.approx(
        16 * 0.0004 + (2.0 if side == "host" else 0.0))


@pytest.mark.parametrize("rates", [
    [0.004] * 8,                                    # steady steps
    [0.010 * 1.2 ** i for i in range(12)],          # spans grow a fifth each
    [0.020 - 0.001 * i for i in range(12)],         # and shrink
    [0.004, 0.0055, 0.004, 0.0055, 0.004],          # under the ratio
])
def test_a_smooth_run_of_intervals_holds_no_stall(rates):
    calls, marks = _timeline(rates, phase="prompt", units=4)
    assert ga.find_stalls(ga.intervals(0.0, calls, marks)) == []


def test_a_short_last_interval_is_judged_a_step():
    """255 steps in marks of 15: the last interval holds 15 like the
    others here, so cut one to 3 steps: a fifth of the seconds at the same
    rate is no stall, and the same seconds as its neighbour's is one."""
    calls, marks = _timeline([0.004] * 4)
    short = ("decode", 64 + 3, marks[-1][2] + 3 * 0.004)
    rows = ga.intervals(0.0, calls, marks + [short])
    assert rows[-1]["units"] == 3 and ga.find_stalls(rows) == []
    slow = ("decode", 64 + 3, marks[-1][2] + 16 * 0.004)
    [stall] = ga.find_stalls(ga.intervals(0.0, calls, marks + [slow]))
    assert stall["index"] == 4 and stall["units"] == 3
    assert stall["excess"] == pytest.approx(13 * 0.004)


def test_a_span_is_held_to_spans_and_a_step_to_steps():
    """A prompt whose spans take a hundred steps' time each is no stall
    beside the steps, and one program's prompt cannot be judged."""
    spans, marks = _timeline([0.4] * 3, phase="prompt", units=4)
    steps, more = _timeline([0.004] * 3)
    offset = marks[-1][2]
    calls = spans + [(a + offset, b + offset) for a, b in steps]
    marks = marks + [(p, at, t + offset) for p, at, t in more]
    rows = ga.intervals(0.0, calls, marks)
    assert [row["phase"] for row in rows] == ["prompt"] * 3 + ["decode"] * 3
    assert ga.find_stalls(rows) == []
    alone = ga.intervals(0.0, [(0.0, 5.0)], [("prompt", 1, 5.0)] + [
        ("decode", at, 5.0 + 0.004 * at) for at in (16, 32, 48)])
    assert ga.find_stalls(alone) == []


@pytest.mark.parametrize("tail, side, found", [
    (0.001, "device", None),        # shorter than a step
    (0.005, "device", None),        # a step and a quarter
    (0.5, "device", "device"),      # the host sat in a read-back
    (0.5, "host", "host"),          # the host was elsewhere
])
def test_the_tail_is_held_to_the_steps_before_it(tail, side, found):
    calls, marks = _timeline([0.004] * 4)
    end = marks[-1][2]
    if side == "device":
        calls = calls + [(end, end + tail)]
    rows = ga.intervals(0.0, calls, marks + [("tail", 1, end + tail)])
    assert (rows[-1]["phase"], rows[-1]["units"]) == ("tail", 1)
    stalls = ga.find_stalls(rows)
    if found is None:
        assert stalls == []
        return
    [stall] = stalls
    assert (stall["index"], stall["side"]) == (4, found)
    assert stall["excess"] == pytest.approx(tail - 0.004)


def test_a_tail_after_a_prompt_alone_cannot_be_judged():
    rows = ga.intervals(0.0, [(0.0, 5.0)], [("prompt", 1, 5.0),
                                            ("tail", 1, 9.0)])
    assert ga.find_stalls(rows) == []


def test_a_call_that_spans_a_mark_is_split_between_its_intervals():
    rows = ga.intervals(10.0, [(10.5, 12.5), (13.0, 13.5)],
                        [("decode", 4, 12.0), ("decode", 8, 14.0)])
    assert [row["seconds"] for row in rows] == [2.0, 2.0]
    assert [row["between"] for row in rows] == pytest.approx([0.5, 1.0])


def test_a_collection_inside_a_call_is_the_hosts_all_the_same():
    """A dispatch is Python before it is the runtime's: a collection that
    falls into it stops the thread inside a call. The rows' `gc` seconds
    put the interval on the host's side."""
    calls, marks = _timeline([0.004] * 6, slow=2, side="device")
    rows = ga.intervals(0.0, calls, marks)
    [stall] = ga.find_stalls(rows)
    assert stall["side"] == "device"
    rows[2]["gc"] = 1.9
    [stall] = ga.find_stalls(rows)
    assert (stall["index"], stall["side"]) == (2, "host")


def test_a_stall_that_two_intervals_share_counts_over_the_slower_neighbour():
    """The rule holds an interval to the LARGER of its neighbours: of two
    slow ones side by side neither is 1.5 times the other."""
    calls, marks = _timeline([0.004, 0.004, 0.02, 0.02, 0.004, 0.004])
    assert ga.find_stalls(ga.intervals(0.0, calls, marks)) == []


# -- a stall the host makes ------------------------------------------------

def test_a_step_callback_that_sleeps_shows_as_a_host_stall(caplog):
    built = decode.build_decode_pipeline(
        "pipeedge/test-tiny-gpt2", None, max_len=64, dtype=jnp.float32)
    new = 36                # marks every 2 tokens
    np.asarray(built.generate(_ids(), new))

    def nap(step, token):
        if step == 17:
            time.sleep(0.25)
    before = _counters()
    with caplog.at_level(logging.WARNING, logger=ga.logger.name):
        np.asarray(built.generate(_ids(1), new, step_callback=nap))
    account = built.batch_accounts[-1]
    stall = max(account["stalls"], key=lambda s: s["excess"])
    assert stall["side"] == "host" and stall["phase"] == "decode"
    assert 0.2 < stall["excess"] < 0.5 and stall["between"] >= 0.25
    assert stall["at"] - stall["units"] <= 18 <= stall["at"] + 2
    gained = _gained(before)
    assert gained[("pipeedge_generate_stall_seconds_total",
                   (("side", "host"),))] >= stall["excess"]
    # one line a stalled batch, with the batch's account
    [line] = [r.getMessage() for r in caplog.records
              if "stalled batch" in r.getMessage()]
    assert "host side +0." in line and '"marks"' in line


# -- the families ---------------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_family_renders_its_whole_matrix_before_any_batch(family):
    """Declared at import: a scrape sees every series from the start."""
    rendered = [line for line in metrics.REGISTRY.render().splitlines()
                if line.split("{")[0].split(" ")[0] == family]
    assert len(rendered) == len(FAMILIES[family])
    for labels in FAMILIES[family]:
        assert any(all(f'{k}="{v}"' in line for k, v in labels.items())
                   for line in rendered)


def test_the_account_and_its_caller_are_pipelint_clean():
    """PL501: every labelled family declares its matrix; PL502: every
    span is entered by a `with`."""
    findings, errors, _ = lint.run_lint([
        os.path.join(REPO, "pipeedge_tpu", "telemetry",
                     "generate_account.py"),
        os.path.join(REPO, "pipeedge_tpu", "parallel", "decode.py")])
    assert not errors
    assert [f for f in findings if f.rule in ("PL501", "PL502")] == []
