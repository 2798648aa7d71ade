"""Switch-MoE decoder family: routed FFN blocks through the shard engine,
pipeline drivers, KV-cache decoding, and the ep mesh axis."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from pipeedge_tpu.models import ShardConfig
from pipeedge_tpu.models import gpt2 as gpt2_mod
from pipeedge_tpu.models.layers import TransformerConfig, gelu, gelu_new
from pipeedge_tpu.models.registry import get_model_config
from pipeedge_tpu.models.shard import make_shard_fn
from pipeedge_tpu.parallel import decode, expert, spmd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "pipeedge/test-tiny-moe"


@pytest.fixture(scope="module")
def moe_setup():
    cfg = get_model_config(MODEL)
    weights = gpt2_mod.moe_state_dict(cfg, seed=3)
    return cfg, weights


def _shard(cfg, weights, l, r):
    total = 4 * cfg.num_hidden_layers
    sc = ShardConfig(l, r, is_first=l == 1, is_last=r == total)
    return gpt2_mod.load_params(cfg, sc, weights), sc


def test_moe_delta_matches_reference_ffn(moe_setup):
    """moe_ffn_delta == reference_moe_ffn - input (same routing/capacity)."""
    cfg, _ = moe_setup
    params = expert.init_moe_params(cfg, n_experts=4, seed=1)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 8, 32)),
                    jnp.float32)
    delta = expert.moe_ffn_delta(params, x, 4, 1.25, act=gelu)
    full = expert.reference_moe_ffn(params, x, 4)
    np.testing.assert_allclose(np.asarray(delta), np.asarray(full - x),
                               rtol=2e-5, atol=2e-5)


def test_moe_delta_matches_ep_sharded(moe_setup):
    """The family's FFN math == the ep-sharded switch-FFN over 2 devices
    (same act), so MoE blocks and the 'ep' axis share one semantics."""
    cfg, _ = moe_setup
    params = expert.init_moe_params(cfg, n_experts=4, seed=4)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("ep",))
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 6, 32)),
                    jnp.float32)
    ep_fn = expert.make_ep_ffn_fn(cfg, mesh, n_experts=4, act=gelu_new)
    ep_out = ep_fn(expert.shard_moe_params(params, mesh), x)
    delta = expert.moe_ffn_delta(params, x, 4, 1.25, act=gelu_new)
    np.testing.assert_allclose(np.asarray(ep_out - x), np.asarray(delta),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("partition", [
    [(1, 8)],
    [(1, 4), (5, 8)],
    [(1, 3), (4, 8)],     # cut after the MoE sublayer: (delta, res) edge
    [(1, 7), (8, 8)],
])
def test_moe_split_matches_whole(moe_setup, partition):
    cfg, weights = moe_setup
    ids = jnp.asarray(np.random.default_rng(7).integers(0, 100, size=(2, 9)),
                      jnp.int32)
    whole, sc = _shard(cfg, weights, 1, 8)
    expected = np.asarray(make_shard_fn(gpt2_mod.FAMILY, cfg, sc)(whole, ids))
    data = ids
    for l, r in partition:
        params, sc = _shard(cfg, weights, l, r)
        data = make_shard_fn(gpt2_mod.FAMILY, cfg, sc)(params, data)
    np.testing.assert_allclose(np.asarray(data), expected, rtol=2e-5,
                               atol=2e-5)
    assert expected.shape == (2, 9, 100)


@pytest.mark.slow
def test_moe_spmd_pipeline(moe_setup):
    """MoE blocks through the one-program SPMD pipeline (pp x dp).

    Under dp the batch is sharded, and capacity routing — which depends on
    the token set — runs per dp shard (the standard data-parallel MoE
    semantics: each group routes its own tokens). The oracle therefore
    routes each half-batch independently."""
    cfg, weights = moe_setup
    partition = [(1, 4), (5, 8)]
    stage_params = [_shard(cfg, weights, l, r)[0] for l, r in partition]
    mesh = spmd.make_pipeline_mesh(2, dp=2)
    pipe = spmd.build_spmd_pipeline(gpt2_mod.FAMILY, cfg, partition,
                                    stage_params, mesh)
    ids = jnp.asarray(
        np.random.default_rng(8).integers(0, 100, size=(3, 4, 8)), jnp.int32)
    got = np.asarray(pipe.run(ids))
    whole, sc = _shard(cfg, weights, 1, 8)
    fn = make_shard_fn(gpt2_mod.FAMILY, cfg, sc)
    expected = np.stack([
        np.concatenate([np.asarray(fn(whole, u[:2])),
                        np.asarray(fn(whole, u[2:]))]) for u in ids])
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)
    with pytest.raises(NotImplementedError, match="tp.*sp|MoE"):
        spmd.build_spmd_pipeline(gpt2_mod.FAMILY, cfg, partition,
                                 stage_params,
                                 spmd.make_pipeline_mesh(2, tp=2))


@pytest.mark.slow
def test_moe_decode_matches_forward_greedy(moe_setup):
    """KV-cache greedy decode == no-cache greedy (full forward per step)."""
    cfg, weights = moe_setup
    partition = [(1, 4), (5, 8)]
    stage_params = [_shard(cfg, weights, l, r)[0] for l, r in partition]
    pipe = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                 stage_params, max_len=16)
    ids = np.random.default_rng(9).integers(0, 100, size=(2, 5))
    got = np.asarray(pipe.generate(ids, 6))

    whole, sc = _shard(cfg, weights, 1, 8)
    fn = make_shard_fn(gpt2_mod.FAMILY, cfg, sc)
    seq = np.array(ids)
    for _ in range(6):
        logits = np.asarray(fn(whole, jnp.asarray(seq, jnp.int32)))
        seq = np.concatenate([seq, logits[:, -1].argmax(-1)[:, None]], axis=1)
    np.testing.assert_array_equal(got, seq)
    with pytest.raises(NotImplementedError, match="MoE"):
        decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition, stage_params,
                              max_len=16,
                              mesh=Mesh(np.asarray(jax.devices()[:2]),
                                        ("tp",)))


@pytest.mark.slow
def test_moe_ep_decode_matches_plain(moe_setup):
    """Expert-parallel MoE decode: experts shard over an 'ep' mesh inside
    the decode step (global routing, local expert slab, one psum), cache
    replicated — same tokens as the single-device pipeline (top-1 routing
    means the psum adds exactly one nonzero term, so this is exact)."""
    cfg, weights = moe_setup
    partition = [(1, 4), (5, 8)]
    stage_params = [_shard(cfg, weights, l, r)[0] for l, r in partition]
    ids = np.random.default_rng(13).integers(0, 100, size=(2, 5))
    plain = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                  stage_params, max_len=16)
    want = np.asarray(plain.generate(ids, 6))
    ep_mesh = Mesh(np.asarray(jax.devices()[:2]), ("ep",))
    piped = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                  stage_params, max_len=16, ep_mesh=ep_mesh)
    got = np.asarray(piped.generate(ids, 6))
    np.testing.assert_array_equal(got, want)
    # int8 KV composes with ep (cache + per-head scale rows replicated
    # across the ep axis -> identical quantization on every device):
    # tokens match the single-device int8 MoE pipeline
    int8_plain = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                       stage_params, max_len=16,
                                       cache_bits=8)
    int8_ep = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                    stage_params, max_len=16,
                                    cache_bits=8, ep_mesh=ep_mesh)
    np.testing.assert_array_equal(
        np.asarray(int8_ep.generate(ids, 6)),
        np.asarray(int8_plain.generate(ids, 6)))
    with pytest.raises(ValueError, match="requires an MoE config"):
        decode.make_ep_stage_fns(
            gpt2_mod.FAMILY,
            TransformerConfig(model_type="gpt2", hidden_size=32,
                              num_hidden_layers=2, num_attention_heads=4,
                              intermediate_size=64, vocab_size=100,
                              max_position_embeddings=64),
            ShardConfig(1, 8, is_first=True, is_last=True), ep_mesh, {})


@pytest.mark.slow
def test_moe_tp_ep_decode_matches_plain(moe_setup):
    """The MoE serving composition: attention
    tp-sharded AND experts ep-sharded in ONE ('tp','ep') mesh per decode
    stage. Exact vs the single-device pipeline: attention psums over tp
    reproduce the dense result, routing sees the full token set, and the
    expert psum over ep adds one nonzero term per token."""
    cfg, weights = moe_setup
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for a 2x2 tp x ep mesh")
    partition = [(1, 4), (5, 8)]
    stage_params = [_shard(cfg, weights, l, r)[0] for l, r in partition]
    ids = np.random.default_rng(17).integers(0, 100, size=(2, 5))
    plain = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                  stage_params, max_len=16)
    want = np.asarray(plain.generate(ids, 6))
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("tp", "ep"))
    piped = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                  stage_params, max_len=16, tp_ep_mesh=mesh)
    got = np.asarray(piped.generate(ids, 6))
    np.testing.assert_array_equal(got, want)

    # guard rails: dense configs refuse (use plain tp), bad divisibility
    # refuses, and tp_ep_mesh does not stack with the single-axis meshes
    dense_cfg = TransformerConfig(
        model_type="gpt2", hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64, vocab_size=100,
        max_position_embeddings=64)
    with pytest.raises(ValueError, match="requires an MoE config"):
        decode.make_tp_ep_stage_fns(
            gpt2_mod.FAMILY, dense_cfg,
            ShardConfig(1, 8, is_first=True, is_last=True), mesh, {})
    with pytest.raises(ValueError, match="does not compose|replaces"):
        decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition, stage_params,
                              max_len=16, tp_ep_mesh=mesh,
                              ep_mesh=Mesh(np.asarray(jax.devices()[:2]),
                                           ("ep",)))


@pytest.mark.fleet
def test_moe_runtime_cli(tmp_path):
    """MoE decoder end-to-end through the runtime CLI (host driver with a
    quantized (delta, residual) edge, then the SPMD driver)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO)
    for extra in (["-pt", "1,3,4,8", "-q", "8,0"], ["-c", "spmd"]):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "runtime.py"), "0", "2",
             "-m", MODEL, "-b", "4", "-u", "2"] + extra
            + ([] if "-pt" in extra else ["-pt", "1,4,5,8"]),
            capture_output=True, env=env, cwd=str(tmp_path), text=True,
            timeout=300)
        assert proc.returncode == 0, proc.stderr
        assert "throughput_items_sec=" in proc.stdout


@pytest.mark.fleet
def test_moe_save_weights_roundtrip(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "save_model_weights.py"),
         "-m", MODEL, "--random"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert os.path.exists("test-tiny-moe.npz")
    from pipeedge_tpu.models import registry
    fn, params, _ = registry.module_shard_factory(
        MODEL, "test-tiny-moe.npz", 1, 8)
    block0 = params["blocks"][0] if isinstance(params["blocks"], tuple) \
        else jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
    assert block0["moe"]["router"]["w"].shape == (32, 4)
    assert block0["moe"]["experts"]["mlp_up"]["w"].shape == (4, 32, 64)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 100, size=(2, 7)),
                      jnp.int32)
    out = np.asarray(fn(params, ids))
    assert out.shape == (2, 7, 100) and np.all(np.isfinite(out))


@pytest.mark.slow
def test_moe_sp_prefill_matches_plain(moe_setup):
    """Droppless MoE (capacity_factor == n_experts, routing a pure
    per-token gate) supports sequence-parallel prefill: chunk-local
    routing is exact, so tokens match the plain pipeline. A
    capacity-BOUNDED MoE config still refuses (chunk-local capacity
    changes drop semantics)."""
    import dataclasses

    cfg, weights = moe_setup
    assert cfg.capacity_factor >= cfg.n_experts
    partition = [(1, 4), (5, 8)]
    stage_params = [_shard(cfg, weights, l, r)[0] for l, r in partition]
    plain = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                  stage_params, max_len=16)
    ids = np.random.default_rng(21).integers(0, 100, size=(2, 6))
    want = np.asarray(plain.generate(ids, 5))

    sp_mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    sp_pipe = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                    stage_params, max_len=16,
                                    sp_mesh=sp_mesh)
    got = np.asarray(sp_pipe.generate(ids, 5))
    np.testing.assert_array_equal(got, want)

    bounded = dataclasses.replace(cfg, capacity_factor=1.25)
    with pytest.raises(NotImplementedError, match="dropless"):
        bounded_pipe = decode.DecodePipeline(
            gpt2_mod.FAMILY, bounded, partition, stage_params, max_len=16,
            sp_mesh=sp_mesh)
        bounded_pipe.generate(ids, 2)
