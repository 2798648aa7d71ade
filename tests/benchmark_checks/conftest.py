"""Fixtures of the benchmark's own tests: a temporary copy of the manifest
whose configurations and traffic are cut to the program's tiny test models,
so that the same runners, readers and references run on the CPU in seconds.
Nothing here describes a TPU topology or touches a chip."""
import json
import os
import shutil
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_VIT = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                intermediate_size=64, num_labels=5, patch_size=4,
                image_size=16, program_model="pipeedge/test-tiny-vit",
                dtype="float32")
TINY_GPT2 = dict(n_embd=32, n_layer=2, n_head=4, vocab_size=100,
                 n_positions=64, n_ctx=64,
                 program_model="pipeedge/test-tiny-gpt2", dtype="float32")
TINY_TRAFFIC = {
    "host-1stage": dict(partition="1,8", ubatch=4, staged_images=32,
                        round_images=16, trace_round_images=8,
                        layer_seconds=0.5, trace_seconds=0.3),
    "spmd-4stage": dict(partition="1,4,5,8", ubatch=4,
                        staged_images=32, round_images=32,
                        trace_round_images=16, layer_seconds=0.5,
                        trace_seconds=0.3),
    "offline-batch": dict(batch=4, prompt_len=8, new_tokens=8, max_len=32,
                          layer_seconds=0.5, trace_seconds=0.3),
    "chat-overload": dict(server_args=["--max-len", 48, "--max-active", 8, "--no-brownout"],
                      weights_file="test-tiny-gpt2.npz", rate_per_s=8.0,
                      prompt_len={"choices": [4, 8, 16]},
                      new_tokens={"log_uniform": [2, 12]},
                      warm_new_tokens=3, layer_seconds=2.0,
                      trace_seconds=0.5, check_pad_to=32),
}


def _load(path):
    with open(path, encoding="utf8") as file:
        return json.load(file)


def _dump(value, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf8") as file:
        json.dump(value, file)


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A copy of BENCHMARK.json, the readers, and every configuration and
    traffic file cut to tiny size, under a temporary root. Compile caches
    of the runs it serves stay under that root too."""
    from benchmark.runners import common
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark", "metrics"),
                    os.path.join(root, "benchmark", "metrics"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    configs = os.path.join(REPO, "benchmark", "configs")
    _dump(dict(_load(os.path.join(configs, "vit-large-patch16-224.json")),
               **TINY_VIT),
          os.path.join(root, "benchmark", "configs",
                       "vit-large-patch16-224.json"))
    _dump(dict(_load(os.path.join(configs, "gpt2-medium.json")), **TINY_GPT2),
          os.path.join(root, "benchmark", "configs", "gpt2-medium.json"))
    for name, cut in TINY_TRAFFIC.items():
        full = _load(os.path.join(REPO, "benchmark", "traffic",
                                  name + ".json"))
        _dump(dict(full, **cut),
              os.path.join(root, "benchmark", "traffic", name + ".json"))
    monkeypatch.setattr(common, "enable_cache", lambda: None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jax_cache"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return root


@pytest.fixture
def run_cell():
    """run_cell(root, cell, trace, seconds, chips) -> (outcome, line): one
    run of a cell on the CPU through the normal runner and readers."""
    import importlib

    from benchmark import run as bench_run

    def run(root, cell, trace=False, seconds=0.5, chips=None, seed=2 ** 31 + 5):
        manifest, ctx = bench_run.context(
            root, cell, seed, seconds, trace, platforms=("cpu",),
            started=time.monotonic())
        if chips is not None:
            ctx.cell = dict(ctx.cell, chips=chips)
        runner = importlib.import_module(
            "benchmark.runners." + ctx.traffic["runner"])
        outcome = runner.run(ctx)
        return outcome, bench_run.result_line(root, manifest, ctx, outcome)
    return run
