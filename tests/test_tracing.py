"""Profiler trace capture (utils/tracing.py) and the one probe's two sinks
(telemetry.span): the span ring and, while a profiler session is live, a
host annotation on the trace's own clock — the trace-viewer integration the
reference lacks entirely (SURVEY.md §5.1)."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from pipeedge_tpu import telemetry
from pipeedge_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _no_recorder():
    telemetry.disable()
    yield
    telemetry.disable()


def _profile_files(root):
    return [os.path.join(dp, f) for dp, _, fs in os.walk(root) for f in fs]


def _newest_xplane(trace_dir):
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]


def _host_events(trace_dir):
    """{event name} over every line of every host plane of the newest
    `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData
    return {event.name
            for plane in ProfileData.from_file(
                _newest_xplane(trace_dir)).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for event in line.events}


def test_trace_captures_profile(tmp_path):
    out = str(tmp_path / "trace")
    with tracing.trace(out):
        with telemetry.span("stage", "traced-region"):
            x = jnp.ones((64, 64))
            jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    files = _profile_files(out)
    assert files, "profiler session produced no files"
    assert any(f.endswith((".xplane.pb", ".trace.json.gz")) for f in files), files


def test_trace_none_is_noop(tmp_path):
    with tracing.trace(None):
        pass  # nothing written, no error
    with tracing.trace(""):
        pass


def test_probe_with_no_sink_is_the_shared_null_span():
    """No recorder, no profiler session: the probe hands out one shared
    no-op object and allocates nothing."""
    assert telemetry.span("exec", "pick") is telemetry._NULL_SPAN
    assert telemetry.span("stage", "exec0", stage=0, rid="q1") \
        is telemetry._NULL_SPAN
    with telemetry.span("exec", "pick"):
        pass


def test_probe_ring_only_records_and_enters_no_annotation():
    rec = telemetry.configure(rank=3)
    probe = telemetry.span("exec", "emit", stage=1, rid="q7")
    assert probe._ann is None       # no session: nothing for the profiler
    with probe:
        pass
    (row,) = rec.snapshot()
    assert (row["cat"], row["name"], row["rank"], row["stage"],
            row["rid"]) == ("exec", "emit", 3, 1, "q7")
    assert row["t1"] >= row["t0"]
    assert rec.digest() == {("exec", "emit", 1): (1, row["t1"] - row["t0"])}


@pytest.mark.parametrize("ring", [False, True])
def test_probe_under_a_live_session_is_a_host_event_on_the_trace(
        tmp_path, ring):
    """Whoever started the session (here `tracing.trace`; the benchmark
    calls `jax.profiler.start_trace` from outside the program), a span is
    a host event named `<cat>/<name>` in the `.xplane.pb` — with no
    recorder configured too — and the ring, where there is one, gets the
    same span."""
    rec = telemetry.configure() if ring else None
    out = str(tmp_path / "trace")
    with tracing.trace(out):
        with telemetry.span("exec", "probe-under-test", rid="q1"):
            jax.block_until_ready(jnp.ones((4,)) + 1)
        telemetry.record("sched", "ring-only-mark", 1, 1)
    names = _host_events(out)
    assert "exec/probe-under-test" in names
    assert not any("ring-only-mark" in name for name in names)
    if ring:
        assert [(s["cat"], s["name"]) for s in rec.snapshot()] == [
            ("exec", "probe-under-test"), ("sched", "ring-only-mark")]
    # the session is over: the probe is back to its cheaper forms
    after = telemetry.span("exec", "after")
    if ring:
        assert after._ann is None
    else:
        assert after is telemetry._NULL_SPAN
