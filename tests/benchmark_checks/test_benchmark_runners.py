"""The runners, readers and references end to end at tiny size on the CPU:
the same code path the chip runs, on the program's tiny test models. Also:
a later PR can add a configuration, a traffic mix and a per-layer metric as
new files and new entries only; and a run without a TPU prints no result."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import correct, manifest as rules, weights

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path):
    with open(path, encoding="utf8") as file:
        return json.load(file)


def _dump(value, path):
    with open(path, "w", encoding="utf8") as file:
        json.dump(value, file)


def test_the_host_pipeline_cell_runs_and_agrees_with_the_reference(
        tiny_root, run_cell):
    outcome, line = run_cell(tiny_root, "vit-l.host-1stage")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"img_per_s", "setup_s"}
    assert line["metrics"]["img_per_s"]["unit"] == "img/s"
    assert line["attempted"] == outcome.observed["images"] > 0
    # float32 program against the float32 reference: rounding only
    facts = outcome.notes["reference"]
    assert facts["max_abs_logit_diff"] < 1e-5 * max(facts["max_abs_logit"], 1)
    assert "breakdown" not in line


def test_the_traced_host_run_reports_layer_metrics_only(tiny_root, run_cell):
    outcome, line = run_cell(tiny_root, "vit-l.host-1stage", trace=True)
    # the CPU has no device plane: device metrics are left out, never
    # filled from a CPU number
    assert set(line["metrics"]) == {"dispatch_ms.host"}
    assert outcome.observed["trace"] is None
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["device"]["platform"] == "cpu"


def test_the_spmd_cell_runs_across_virtual_devices(tiny_root, run_cell):
    # the tiny model has two blocks, so two stages on two of the virtual
    # devices: the same mesh, ppermute edge and tick scan as four
    outcome, line = run_cell(tiny_root, "vit-l.spmd-4stage", chips=2)
    assert line["correct"] is True
    assert outcome.observed["stages"] == 2
    rounds = outcome.notes["rounds"]
    assert outcome.observed["ticks"] == rounds * (8 + 2 - 1)
    _, traced = run_cell(tiny_root, "vit-l.spmd-4stage", trace=True, chips=2)
    assert set(traced["metrics"]) == {"tick_ms.spmd"}


def test_the_generate_cell_runs_and_its_tokens_are_near_greedy(
        tiny_root, run_cell):
    outcome, line = run_cell(tiny_root, "gpt2-m.offline-batch")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"tok_per_s", "setup_s"}
    assert outcome.notes["reference"]["tokens_checked"] == 2 * 8
    assert outcome.observed["decode_steps"] == outcome.notes["batches"] * 7
    _, traced = run_cell(tiny_root, "gpt2-m.offline-batch", trace=True)
    assert set(traced["metrics"]) == {"decode_step_ms"}


def test_the_serving_cell_runs_through_the_server_and_the_generator(
        tiny_root, run_cell):
    outcome, line = run_cell(tiny_root, "gpt2-m.chat-overload", trace=True,
                             seconds=2.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 16          # 8 req/s for 2 s
    assert set(line["metrics"]) == {
        "gen_late_p95_ms", "admit_wait_p95_ms", "rows_per_step",
        "itl_p50_ms.overload", "ttft_p50_ms.overload"}
    # one row a dispatch, counted from the program's own spans
    assert line["metrics"]["rows_per_step"]["value"] == 1.0
    assert outcome.notes["reference"]["tokens_outside"] == 0
    assert outcome.end_to_end["served_tok_per_s"] > 0
    _, plain = run_cell(tiny_root, "gpt2-m.chat-overload", seconds=1.0)
    assert set(plain["metrics"]) == {"served_tok_per_s", "setup_s"}


def test_a_cell_is_added_as_new_files_and_new_entries_only(tiny_root,
                                                           run_cell):
    """What a later PR does: a configuration, a traffic mix, a per-layer
    metric with its reader, and their entries; no file that was there is
    edited, and the harness finds all three by name."""
    before = {}
    for base, _, names in os.walk(tiny_root):
        for name in names:
            path = os.path.join(base, name)
            if name != "BENCHMARK.json":
                with open(path, "rb") as file:
                    before[path] = file.read()
    bench = os.path.join(tiny_root, "benchmark")
    config = _load(os.path.join(bench, "configs",
                                "vit-large-patch16-224.json"))
    _dump(dict(config, name="dummy-vit"),
          os.path.join(bench, "configs", "dummy-vit.json"))
    mix = _load(os.path.join(bench, "traffic", "host-1stage.json"))
    _dump(dict(mix, partition="1,4,5,8", ubatch=2, quant=[0, 0]),
          os.path.join(bench, "traffic", "host-2stage.json"))
    with open(os.path.join(bench, "metrics", "microbatches.dummy.py"), "w",
              encoding="utf8") as file:
        file.write("def read(observed):\n"
                   "    return float(observed['microbatches'])\n")
    manifest = rules.load(tiny_root)
    manifest["configs"].append({
        "name": "dummy-vit", "source": "a test", "reduced": [],
        "file": "benchmark/configs/dummy-vit.json", "why": "a dummy"})
    manifest["workloads"].append({
        "name": "dummy.host-2stage", "config": "dummy-vit",
        "traffic": "host-2stage", "chips": 1, "why": "a dummy cell"})
    manifest["end_to_end"][0]["workloads"].append("dummy.host-2stage")
    manifest["per_layer"].append({
        "name": "microbatches.dummy", "unit": "n", "better": "higher",
        "source": "program_counter", "layer": "host pipeline driver",
        "moves": "img_per_s", "workloads": ["dummy.host-2stage"]})
    _dump(manifest, os.path.join(tiny_root, "BENCHMARK.json"))
    os.makedirs(os.path.join(tiny_root, "tests", "benchmark_checks"))
    assert rules.problems(manifest, tiny_root) == []

    outcome, line = run_cell(tiny_root, "dummy.host-2stage")
    assert line["correct"] is True and outcome.observed["stages"] == 2
    assert set(line["metrics"]) == {"img_per_s", "setup_s"}
    _, traced = run_cell(tiny_root, "dummy.host-2stage", trace=True)
    assert set(traced["metrics"]) == {"microbatches.dummy"}
    for path, content in before.items():
        with open(path, "rb") as file:
            assert file.read() == content, f"{path} was edited"


def test_a_reader_that_finds_nothing_is_left_out(tiny_root, run_cell):
    path = os.path.join(tiny_root, "benchmark", "metrics",
                        "dispatch_ms.host.py")
    with open(path, "w", encoding="utf8") as file:
        file.write("def read(observed):\n    return None\n")
    _, line = run_cell(tiny_root, "vit-l.host-1stage", trace=True)
    assert line["metrics"] == {}


def test_without_a_tpu_the_command_fails_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "vit-l.host-1stage", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert "NoAccelerator" in done.stderr
    assert not [row for row in done.stdout.splitlines()
                if row.startswith("{")]


# -- the comparisons that decide `correct`, on the tiny models --------------

@pytest.fixture
def tiny_gpt2(tiny_root, tmp_path):
    config = _load(os.path.join(tiny_root, "benchmark", "configs",
                                "gpt2-medium.json"))
    path = weights.write(config, 11, str(tmp_path / "w" / "gpt2.npz"))
    return config, path


def test_greedy_tokens_of_the_reference_itself_pass(tiny_gpt2):
    config, path = tiny_gpt2
    with np.load(path) as tensors:
        forward = correct.reference_module(config).forward
        ids = [3, 1, 4, 1, 5]
        for _ in range(6):
            logits = np.asarray(forward(config, tensors, np.array([ids])))
            ids.append(int(logits[0, -1].argmax()))
        ok, facts = correct.tokens_near_greedy(config, tensors, [ids], [5])
        assert ok and facts["tokens_checked"] == 6
        assert facts["worst_gap_share_of_range"] == 0.0
        padded_ok, _ = correct.tokens_near_greedy(
            config, tensors, [ids], [5], pad_to=32)
        assert padded_ok


def test_a_token_that_is_not_near_the_largest_logit_fails(tiny_gpt2):
    config, path = tiny_gpt2
    with np.load(path) as tensors:
        forward = correct.reference_module(config).forward
        ids = [3, 1, 4, 1, 5]
        logits = np.asarray(forward(config, tensors, np.array([ids])))
        worst = int(logits[0, -1].argmin())
        ok, facts = correct.tokens_near_greedy(
            config, tensors, [ids + [worst]], [5])
        assert not ok and facts["tokens_outside"] == 1
        assert facts["worst_gap_share_of_range"] == pytest.approx(1.0)


def test_logits_off_by_more_than_the_tolerance_fail(tiny_root, tmp_path):
    config = _load(os.path.join(tiny_root, "benchmark", "configs",
                                "vit-large-patch16-224.json"))
    path = weights.write(config, 12, str(tmp_path / "w" / "vit.npz"))
    images = np.random.default_rng(0).standard_normal(
        (2, 3, 16, 16)).astype(np.float32)
    with np.load(path) as tensors:
        wanted = np.asarray(correct.reference_module(config).forward(
            config, tensors, images))
        scale = np.abs(wanted).max()
        ok, _ = correct.logits_agree(config, tensors, images,
                                     wanted + 0.01 * scale)
        assert ok           # a bfloat16's worth of error passes
        ok, facts = correct.logits_agree(config, tensors, images,
                                         wanted + 2.0 ** -4 * scale)
        assert not ok       # an 8-bit step does not
        assert facts["max_abs_logit_diff"] > facts["tolerance"]


def test_seeded_weights_repeat_and_differ_by_seed(tiny_root, tmp_path):
    config = _load(os.path.join(tiny_root, "benchmark", "configs",
                                "gpt2-medium.json"))
    one = weights.write(config, 2 ** 31 + 3, str(tmp_path / "a.npz"))
    two = weights.write(config, 2 ** 31 + 3, str(tmp_path / "b.npz"))
    other = weights.write(config, 4, str(tmp_path / "c.npz"))
    with np.load(one) as a, np.load(two) as b, np.load(other) as c:
        assert sorted(a.files) == sorted(b.files) == sorted(c.files)
        assert "lm_head.weight" not in a.files      # tied
        for key in a.files:
            assert np.array_equal(a[key], b[key])
        assert not np.array_equal(a["transformer.wte.weight"],
                                  c["transformer.wte.weight"])
        assert a["transformer.h.0.ln_1.weight"].mean() \
            == pytest.approx(1.0, abs=0.05)
