"""Set-up says where it goes (docs/OBSERVABILITY.md, category `startup`):
the loader's and the three builders' phases on the always-on counter, the
one `jax.monitoring` listener's split of program builds by program and
step, the `startup` span beside its counter, and a server's first scrape."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import prom as bench_prom
from pipeedge_tpu import telemetry
from pipeedge_tpu.models import registry
from pipeedge_tpu.parallel import decode, pipeline, spmd
from pipeedge_tpu.telemetry import metrics as prom
from test_serve import _spawn_server
from test_serving_spans import _get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GPT2, VIT = "pipeedge/test-tiny-gpt2", "pipeedge/test-tiny-vit"
HALVES = [(1, 4), (5, 8)]


@pytest.fixture(scope="module")
def weights(tmp_path_factory):
    """{model: a weights file of seeded random values}, as the CLI makes."""
    out = str(tmp_path_factory.mktemp("weights"))
    files = {}
    for model in (GPT2, VIT):
        subprocess.run(
            [sys.executable, os.path.join(REPO, "save_model_weights.py"),
             "--random", "-m", model, "-o", out], check=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True)
        files[model] = os.path.join(
            out, registry.get_model_default_weights_file(model))
    return files


def _phases():
    """{phase: seconds} and the bytes read, of the process so far."""
    text = prom.REGISTRY.render()
    seconds = {labels["phase"]: value for labels, value in bench_prom.samples(
        text, "pipeedge_startup_seconds_total")}
    [(_, read)] = bench_prom.samples(text, "pipeedge_startup_bytes_total")
    return seconds, read


def _builds(registry_=prom.REGISTRY):
    """{(program, step): builds} of a registry the listener feeds."""
    return {(labels["program"], labels["step"]): value
            for labels, value in bench_prom.samples(
                registry_.render(), "pipeedge_jax_program_builds_total")}


def _gained(before, after):
    return {key: after[key] - before.get(key, 0.0) for key in after
            if after[key] != before.get(key, 0.0)}


def _build_decode(path):
    return decode.build_decode_pipeline(GPT2, None, max_len=48,
                                        model_file=path)


def _build_host(path):
    pipe = pipeline.build_pipeline(VIT, HALVES, model_file=path)
    cfg = registry.get_model_config(VIT)
    pipe.run([jnp.ones((2, cfg.num_channels, cfg.image_size,
                        cfg.image_size))])
    return pipe


def _build_spmd(path):
    entry = registry.get_model_entry(VIT)
    stage_params = [registry.module_shard_factory(
        VIT, path, l, r, stage=i, unroll=False)[1]
        for i, (l, r) in enumerate(HALVES)]
    pipe = spmd.build_spmd_pipeline(
        entry.family.FAMILY, entry.config, HALVES, stage_params,
        spmd.make_pipeline_mesh(2, devices=jax.devices()[:2]))
    cfg = entry.config
    pipe.run(jnp.ones((2, 2, cfg.num_channels, cfg.image_size,
                       cfg.image_size)))
    return pipe


@pytest.mark.parametrize("model, build", [
    (GPT2, _build_decode), (VIT, _build_host), (VIT, _build_spmd)],
    ids=["decode", "host", "spmd"])
def test_a_builder_on_a_weights_file_counts_every_phase(weights, model,
                                                        build):
    """Reading, placing and constructing programs each take time, and what
    was read is the file's arrays, each once (the host and SPMD drivers
    make their program at the first call: it is part of the build here)."""
    with np.load(weights[model]) as arrays:
        nbytes = sum(arrays[key].nbytes for key in arrays)
    seconds0, read0 = _phases()
    build(weights[model])
    seconds, read = _phases()
    assert set(seconds) == set(telemetry.STARTUP_PHASES)
    gained = _gained(seconds0, seconds)
    assert set(gained) == {"weights_read", "weights_place", "programs"}
    assert all(value > 0.0 for value in gained.values())
    assert read - read0 == nbytes


def test_installing_the_listener_twice_counts_a_build_once():
    registry_ = prom.Registry()
    first = prom.count_jax_compiles(registry_)
    assert prom.count_jax_compiles(registry_) == first
    decode.build_decode_pipeline(GPT2, None, max_len=48)    # installs too
    from jax._src import monitoring as jax_monitoring
    assert jax_monitoring.get_event_duration_listeners().count(
        prom._on_build_event) == 1
    step = jax.jit(lambda x: x * 5 - 2)
    x = jax.block_until_ready(jnp.ones((11,), jnp.float32))
    compiles0, mine0 = first[0].value(), prom.REGISTRY.counter(
        "pipeedge_jax_compiles_total", "").value()
    jax.block_until_ready(step(x))
    assert first[0].value() - compiles0 == 1
    # each registry the one listener feeds got it once
    assert prom.REGISTRY.counter("pipeedge_jax_compiles_total",
                                 "").value() - mine0 == 1


def test_a_stage_programs_first_call_is_one_trace_lower_and_compile():
    """Under the program's own name; a second call of the same shape adds
    nothing; an eager operation lands under `other`."""
    pipe = decode.build_decode_pipeline(GPT2, None, max_len=48)
    stage = pipe.stages[0]
    [cache] = pipe._fresh_caches(3)
    ids = jnp.zeros((3, 7), jnp.int32)
    before = _builds()
    assert {program for program, _ in before} \
        == set(prom.PROGRAMS) | {prom.OTHER_PROGRAM}
    assert {step for _, step in before} == set(prom.BUILD_STEPS)
    out, cache = stage["prefill"](stage["params"], ids, cache)
    jax.block_until_ready(out)
    first = _builds()
    gained = _gained(before, first)
    own = {key: value for key, value in gained.items()
           if key[0] != prom.OTHER_PROGRAM}
    assert own in ({("prefill", "trace"): 1, ("prefill", "lower"): 1,
                    ("prefill", "compile"): 1},
                   {("prefill", "trace"): 1, ("prefill", "lower"): 1,
                    ("prefill", "cache_read"): 1})
    [cache] = pipe._fresh_caches(3)
    other = _builds()
    out, cache = stage["prefill"](stage["params"], ids, cache)
    jax.block_until_ready(out)
    assert _builds() == other               # a warm shape builds nothing
    jax.block_until_ready(jnp.zeros((13, 17, 3)))
    eager = _gained(other, _builds())
    assert eager and {program for program, _ in eager} \
        == {prom.OTHER_PROGRAM}


def test_a_program_the_persistent_cache_holds_is_a_cache_read(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {key: getattr(jax.config, key) for key in keys}
    prom.count_jax_compiles()
    try:
        for key, value in zip(keys, (str(tmp_path), 0, -1)):
            jax.config.update(key, value)
        compilation_cache.reset_cache()

        def pick_next(x):
            return jnp.tanh(x) * 3 + x[::-1]
        x = jnp.arange(23, dtype=jnp.float32)
        before = _builds()
        jax.block_until_ready(jax.jit(pick_next)(x))
        filled = _builds()
        assert _gained(before, filled)[("pick_next", "compile")] == 1
        assert os.listdir(tmp_path)
        jax.clear_caches()                  # as a new process would start
        jax.block_until_ready(jax.jit(pick_next)(x))
        again = _gained(filled, _builds())
        assert {key: value for key, value in again.items()
                if key[0] == "pick_next"} \
            == {("pick_next", "trace"): 1, ("pick_next", "lower"): 1,
                ("pick_next", "cache_read"): 1}
    finally:
        for key, value in was.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()


def test_a_trace_inside_a_trace_is_counted_with_the_outer_one():
    """`host_stage_step` calls the jitted `shard_apply`: one trace, whose
    seconds hold the inner one's, and no build under the inner name."""
    pipe = pipeline.build_pipeline(VIT, [(1, 8)])
    cfg = registry.get_model_config(VIT)
    before = _builds()
    pipe.run([jnp.ones((3, cfg.num_channels, cfg.image_size,
                        cfg.image_size))])
    gained = _gained(before, _builds())
    assert gained[("host_stage_step", "trace")] == 1
    assert not any(program == "shard_apply" for program, _ in gained)


def _startup_seconds(phase):
    return prom.REGISTRY.counter("pipeedge_startup_seconds_total",
                                 "").value(phase=phase)


def test_without_a_sink_startup_still_counts_and_records_no_span():
    telemetry.disable()
    assert telemetry.span("startup", "programs") is telemetry._NULL_SPAN
    before = _startup_seconds("programs")
    with telemetry.startup("programs"):
        sum(range(1000))
    assert _startup_seconds("programs") > before
    assert telemetry.recorder() is None


def test_with_a_ring_the_span_is_the_counters_two_readings():
    rec = telemetry.configure()
    try:
        before = _startup_seconds("service")
        with telemetry.startup("service"):
            sum(range(1000))
        gained = _startup_seconds("service") - before
    finally:
        telemetry.disable()
    [span] = [s for s in rec.snapshot() if s["cat"] == "startup"]
    assert span["name"] == "service" and span["stage"] is None
    assert gained == pytest.approx((span["t1"] - span["t0"]) / 1e9,
                                   rel=1e-9, abs=1e-12)


def test_a_phase_inside_a_phase_suspends_the_outer_one():
    """The loader's reads inside its placement: the spans do not overlap,
    so the phases' seconds add up to wall time."""
    rec = telemetry.configure()
    try:
        with telemetry.startup("weights_place"):
            for nbytes in (3, 4):
                with telemetry.startup("weights_read") as phase:
                    phase.moved(nbytes)
    finally:
        telemetry.disable()
    spans = [s for s in rec.snapshot() if s["cat"] == "startup"]
    assert [s["name"] for s in spans] == [
        "weights_place", "weights_read", "weights_place", "weights_read",
        "weights_place"]
    assert all(a["t1"] <= b["t0"] for a, b in zip(spans, spans[1:]))
    with pytest.raises(ValueError, match="no declared start-up phase"):
        telemetry.startup("warm_up")


def test_the_operators_line_reads_the_same_counters():
    line = telemetry.startup_line()
    assert line.startswith("startup: weights ")
    assert "GB read in" in line and "programs built" in line
    assert "compiled," in line and "read) in" in line


@pytest.fixture(scope="module")
def server():
    yield from _spawn_server()


@pytest.mark.fleet
def test_a_servers_first_scrape_has_the_four_families_declared(server):
    text = _get(server, "/metrics")
    seconds = {labels["phase"]: value for labels, value in bench_prom.samples(
        text, "pipeedge_startup_seconds_total")}
    assert set(seconds) == set(telemetry.STARTUP_PHASES)
    # random weights here: no file was read; every other phase took time
    assert seconds.pop("weights_read") == 0.0
    assert all(value > 0.0 for value in seconds.values()), seconds
    assert bench_prom.samples(text, "pipeedge_startup_bytes_total") \
        == [({"phase": "weights_read"}, 0.0)]
    matrix = {(program, step)
              for program in prom.PROGRAMS + (prom.OTHER_PROGRAM,)
              for step in prom.BUILD_STEPS}
    for family in ("pipeedge_jax_program_builds_total",
                   "pipeedge_jax_program_build_seconds_total"):
        assert {(labels["program"], labels["step"])
                for labels, _ in bench_prom.samples(text, family)} == matrix
    spans = [s for s in json.loads(_get(server, "/debug/spans"))["spans"]
             if s["cat"] == "startup"]
    assert {s["name"] for s in spans} >= {"backend", "weights_place",
                                          "programs", "service"}
