"""Weights file generation + loading round trip (offline --random path)."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu.models import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


pytestmark = pytest.mark.fleet  # every test here spawns OS processes

@pytest.mark.parametrize("model", ["pipeedge/test-tiny-vit",
                                   "pipeedge/test-tiny-bert",
                                   "pipeedge/test-tiny-gpt2"])
def test_save_random_weights_and_load(model, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "save_model_weights.py"),
         "-m", model, "--random"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    weights_file = registry.get_model_default_weights_file(model)
    assert os.path.exists(weights_file)

    # factory must load from the file (not fall back to random init)
    layers = registry.get_model_layers(model)
    fn, params, _ = registry.module_shard_factory(model, weights_file, 1, layers)
    cfg = registry.get_model_config(model)
    if cfg.vocab_size:  # token models: BERT and GPT-2
        x = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(2, 9)), dtype=jnp.int32)
    else:
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 3, cfg.image_size, cfg.image_size)), dtype=jnp.float32)
    out = np.asarray(fn(params, x))
    assert np.all(np.isfinite(out))
    assert out.shape[0] == 2

    # partial shard loads only its own keys without error
    fn2, params2, _ = registry.module_shard_factory(model, weights_file, 2, 5)
    assert "embeddings" not in params2 and "final" not in params2


def test_read_checkpoint_keys_tool(tmp_path):
    np.savez(tmp_path / "w.npz", **{"a/b": np.zeros((2, 3))})
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "read_checkpoint_keys.py"),
         str(tmp_path / "w.npz")],
        capture_output=True, env=env, text=True, timeout=60)
    assert proc.returncode == 0
    assert "a/b   (2, 3)" in proc.stdout


def test_create_playbook_tool(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "create_playbook.py"),
         "-wz", "4", "-nz", "hostA,hostB", "-sn", str(tmp_path / "pb.yml")],
        capture_output=True, env=env, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    content = open(tmp_path / "pb.yml").read()
    assert "- hosts: hostA" in content and "runtime.py 0 4" in content


def test_full_size_model_npz_to_logits_pipeline(tmp_path, monkeypatch):
    """Real-checkpoint path on a full-size registry model (the reference's
    families, not the test-tiny oracles): save_model_weights --random ->
    .npz -> per-stage key slicing -> logits, with a mid-block split matching
    the whole-model forward bit-for-bit."""
    monkeypatch.chdir(tmp_path)
    model = "facebook/deit-tiny-distilled-patch16-224"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "save_model_weights.py"),
         "-m", model, "--random"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr
    weights_file = registry.get_model_default_weights_file(model)
    assert os.path.exists(weights_file)

    cfg = registry.get_model_config(model)
    layers = registry.get_model_layers(model)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 3, cfg.image_size, cfg.image_size)), dtype=jnp.float32)

    fn, params, _ = registry.module_shard_factory(model, weights_file, 1,
                                                  layers)
    whole = np.asarray(fn(params, x))
    assert whole.shape == (2, cfg.num_labels)
    assert np.all(np.isfinite(whole))

    # mid-block cut (sublayer 2 of block 6): each stage loads only its own
    # keys from the SAME npz (reference per-stage lazy loading, vit.py:93-118)
    cut = 22
    fn_a, params_a, _ = registry.module_shard_factory(model, weights_file,
                                                      1, cut)
    fn_b, params_b, _ = registry.module_shard_factory(model, weights_file,
                                                      cut + 1, layers)
    piped = np.asarray(fn_b(params_b, fn_a(params_a, x)))
    np.testing.assert_array_equal(piped, whole)


def test_full_size_model_npz_runtime_cli(tmp_path, monkeypatch):
    """End-to-end runtime CLI on a full-size model with a real weights file:
    2-stage host pipeline with a quantized edge."""
    monkeypatch.chdir(tmp_path)
    model = "facebook/deit-tiny-distilled-patch16-224"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "save_model_weights.py"),
         "-m", model, "--random"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr
    weights_file = registry.get_model_default_weights_file(model)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "runtime.py"), "0", "2",
         "--platform", "cpu", "-m", model, "-M", weights_file,
         "-b", "8", "-u", "4", "-pt", "1,24,25,48", "-q", "8,0"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "latency_sec=" in proc.stdout
