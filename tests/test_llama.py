"""LLaMA family (RoPE / RMSNorm / SwiGLU / GQA) vs HF torch, through the
shard engine, pipeline splits, and the KV-cache decode subsystem."""
import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pipeedge_tpu.models import ShardConfig, stage_cache  # noqa: E402
from pipeedge_tpu.models import llama as llama_mod  # noqa: E402
from pipeedge_tpu.models.layers import TransformerConfig  # noqa: E402
from pipeedge_tpu.models.registry import get_model_config  # noqa: E402
from pipeedge_tpu.models.shard import make_shard_fn  # noqa: E402
from pipeedge_tpu.parallel import decode  # noqa: E402

MODEL = "pipeedge/test-tiny-llama"


@pytest.fixture(scope="module")
def llama_setup():
    from transformers import LlamaConfig, LlamaForCausalLM
    cfg = get_model_config(MODEL)
    hf_cfg = LlamaConfig(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.kv_heads,
        intermediate_size=cfg.intermediate_size, vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.layer_norm_eps, rope_theta=cfg.rope_theta,
        attention_bias=False, mlp_bias=False, tie_word_embeddings=False)
    torch.manual_seed(11)
    model = LlamaForCausalLM(hf_cfg).eval()
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    return cfg, weights, model


def _stage_params(cfg, partition, weights):
    total = 4 * cfg.num_hidden_layers
    return [llama_mod.load_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == total), weights)
        for l, r in partition]


def test_config_is_gqa():
    cfg = get_model_config(MODEL)
    assert cfg.kv_heads == 2 and cfg.num_attention_heads == 4


def test_forward_matches_hf(llama_setup):
    """Whole-model shard logits == HF LlamaForCausalLM logits (RoPE,
    RMSNorm, SwiGLU, and the 2-of-4 GQA head grouping all in play)."""
    cfg, weights, model = llama_setup
    total = 4 * cfg.num_hidden_layers
    sc = ShardConfig(1, total, is_first=True, is_last=True)
    params = llama_mod.load_params(cfg, sc, weights)
    fn = make_shard_fn(llama_mod.FAMILY, cfg, sc)
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, size=(2, 9))
    got = np.asarray(fn(params, jnp.asarray(ids, jnp.int32)))
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).logits.numpy()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("partition", [
    [(1, 4), (5, 8)],
    [(1, 3), (4, 8)],      # mid-block cut: 2-tuple (ctx, residual) edge
    [(1, 6), (7, 8)],      # mid-block cut at the MLP edge
])
def test_split_pipeline_matches_whole(llama_setup, partition):
    cfg, weights, model = llama_setup
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, size=(2, 7))
    data = jnp.asarray(ids, jnp.int32)
    total = 4 * cfg.num_hidden_layers
    for l, r in partition:
        sc = ShardConfig(l, r, is_first=l == 1, is_last=r == total)
        params = llama_mod.load_params(cfg, sc, weights)
        data = make_shard_fn(llama_mod.FAMILY, cfg, sc)(params, data)
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).logits.numpy()
    np.testing.assert_allclose(np.asarray(data), want, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_greedy_decode_matches_hf_generate(llama_setup):
    """Pipelined KV-cache greedy decode == HF generate(do_sample=False):
    the GQA cache ([*, kv_heads * Dh]) and per-step RoPE rotation are
    exercised across a 2-stage partition."""
    cfg, weights, model = llama_setup
    partition = [(1, 4), (5, 8)]
    pipe = decode.DecodePipeline(
        llama_mod.FAMILY, cfg, partition,
        _stage_params(cfg, partition, weights), max_len=32)
    cache = stage_cache.init_cache(cfg, 1, 2, 8)
    assert cache["k"].shape[3] == cfg.kv_heads * cfg.head_dim   # GQA-sized
    ids = np.random.default_rng(7).integers(0, cfg.vocab_size, size=(2, 6))
    got = np.asarray(pipe.generate(ids, new_tokens=8))
    with torch.no_grad():
        want = model.generate(torch.from_numpy(ids), max_new_tokens=8,
                              do_sample=False, pad_token_id=0).numpy()
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow
def test_continuous_batching_and_wave_decode(llama_setup):
    """The llama family rides the serving stack unchanged: host continuous
    batching AND the SPMD wave decoder produce the same tokens as solo
    generate() via the family's cached_block_step/decode_embed hooks."""
    from jax.sharding import Mesh

    from pipeedge_tpu.parallel.batcher import ContinuousBatcher
    from pipeedge_tpu.parallel.spmd_decode import SpmdDecodePipeline
    cfg, weights, _ = llama_setup
    partition = [(1, 4), (5, 8)]
    stage_params = _stage_params(cfg, partition, weights)
    pipe = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition,
                                 stage_params, max_len=32)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, size=(1, 6))
               for _ in range(2)]
    solo = [np.asarray(pipe.generate(p, new_tokens=5)) for p in prompts]

    batcher = ContinuousBatcher(pipe)
    for i, p in enumerate(prompts):
        batcher.submit(i, p, new_tokens=5)
    results = batcher.run()
    for i in range(2):
        np.testing.assert_array_equal(results[i], solo[i])

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("stage",))
    wave = SpmdDecodePipeline(llama_mod.FAMILY, cfg, partition,
                              stage_params, mesh, max_len=32)
    got = np.asarray(wave.generate(np.stack(prompts), new_tokens=5))
    for i in range(2):
        np.testing.assert_array_equal(got[i], solo[i])


@pytest.mark.slow
def test_tp_block_and_spmd_tp_pipeline(llama_setup):
    """Megatron TP for llama (GQA column/row table + RoPE/SwiGLU body):
    a tp-sharded block matches the unsharded sublayer chain, and the
    pp x tp SPMD pipeline matches the single-shard forward. tp=2 leaves
    1 kv head per shard — the GQA grouping stays shard-local."""
    from jax.sharding import Mesh

    from pipeedge_tpu.parallel import spmd
    from pipeedge_tpu.parallel.tensor import (make_tp_block_fn,
                                              shard_block_params)
    cfg, weights, _ = llama_setup
    total = 4 * cfg.num_hidden_layers
    sc = ShardConfig(1, total, is_first=True, is_last=True)
    params = llama_mod.load_params(cfg, sc, weights)
    bp = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = np.random.default_rng(13).normal(size=(2, 9, 32)).astype(np.float32)
    data = jnp.asarray(x)
    for sub in range(4):
        data = llama_mod.sublayer(bp, sub, data, cfg)
    expected = np.asarray(data)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    fn = make_tp_block_fn(cfg, mesh)
    got = np.asarray(fn(shard_block_params(cfg, bp, mesh), jnp.asarray(x)))
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)

    partition = [(1, 4), (5, 8)]
    pipe_mesh = spmd.make_pipeline_mesh(2, tp=2)
    pipe = spmd.build_spmd_pipeline(
        llama_mod.FAMILY, cfg, partition,
        _stage_params(cfg, partition, weights), pipe_mesh)
    ids = np.random.default_rng(15).integers(0, cfg.vocab_size,
                                             size=(3, 2, 9))
    got = np.asarray(pipe.run(jnp.asarray(ids, jnp.int32)))
    whole = make_shard_fn(llama_mod.FAMILY, cfg, sc)
    expected = np.stack([np.asarray(whole(params, jnp.asarray(u, jnp.int32)))
                         for u in ids])
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)

    # tp DECODE: the family's tp cached step (RoPE on local heads, GQA
    # cache slice, vocab-sharded RMS head) is token-identical to the
    # single-device pipeline
    plain = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition,
                                  _stage_params(cfg, partition, weights),
                                  max_len=32)
    tp_pipe = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition,
                                    _stage_params(cfg, partition, weights),
                                    max_len=32, mesh=mesh)
    dec_ids = np.random.default_rng(17).integers(0, cfg.vocab_size,
                                                 size=(2, 6))
    np.testing.assert_array_equal(
        np.asarray(tp_pipe.generate(dec_ids, new_tokens=6)),
        np.asarray(plain.generate(dec_ids, new_tokens=6)))


@pytest.mark.slow
def test_beam_chunked_prefill_and_int8_compose(llama_setup):
    """The decode feature matrix is family-agnostic where it should be:
    beam search (width 1 == greedy), chunked prefill (token-identical),
    and the int8 GQA cache (close to exact) all run on llama unchanged."""
    cfg, weights, _ = llama_setup
    partition = [(1, 4), (5, 8)]
    sp = _stage_params(cfg, partition, weights)
    pipe = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition, sp,
                                 max_len=32)
    ids = np.random.default_rng(19).integers(0, cfg.vocab_size, size=(4, 6))
    want = np.asarray(pipe.generate(ids, 6))
    np.testing.assert_array_equal(
        np.asarray(pipe.generate_beam(ids, 6, beams=1)), want)
    beam3 = np.asarray(pipe.generate_beam(ids, 4, beams=3))
    assert beam3.shape == (4, 10)
    np.testing.assert_array_equal(
        np.asarray(pipe.generate(ids, 6, prefill_ubatch=2)), want)

    int8 = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition, sp,
                                 max_len=32, cache_bits=8)
    out8 = np.asarray(int8.generate(ids, 6))
    assert out8.shape == want.shape
    assert (out8[:, :6] == ids).all()
    # int8 error may flip late greedy picks on a random tiny model; the
    # first continuation token comes from exact (fresh-row) attention
    np.testing.assert_array_equal(out8[:, 6], want[:, 6])


def test_sp_refused(llama_setup):
    """RoPE makes chunk-local sp attention position-wrong; the FORWARD
    sp override refuses (the decode sp prefill instead pre-rotates at
    global chunk positions via the family hook — tested below)."""
    cfg, weights, _ = llama_setup
    with pytest.raises(NotImplementedError, match="RoPE|sequence"):
        llama_mod.sublayer({}, 0, jnp.zeros((1, 4, 32)), cfg,
                           attention_fn=lambda *a, **k: None)


@pytest.mark.slow
def test_sp_prefill_matches_plain(llama_setup):
    """Sequence-parallel llama prefill: RoPE at global chunk positions
    before the causal ring core, unrepeated post-RoPE GQA rows gathered
    into the cache — decode tokens match the single-device pipeline."""
    from jax.sharding import Mesh
    cfg, weights, _ = llama_setup
    partition = [(1, 4), (5, 8)]
    sp = _stage_params(cfg, partition, weights)
    plain = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition, sp,
                                  max_len=32)
    sp_mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    piped = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition, sp,
                                  max_len=32, sp_mesh=sp_mesh)
    ids = np.random.default_rng(23).integers(0, cfg.vocab_size, size=(2, 6))
    np.testing.assert_array_equal(
        np.asarray(piped.generate(ids, 6)),
        np.asarray(plain.generate(ids, 6)))


@pytest.mark.fleet
@pytest.mark.slow
def test_registry_roundtrip_and_cli(tmp_path):
    """save_model_weights --random -> npz -> factory logits; generate.py
    decodes the tiny llama end-to-end."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "save_model_weights.py"),
         "-m", MODEL, "--random"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert os.path.exists(str(tmp_path / "test-tiny-llama.npz"))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "generate.py"),
         "-m", MODEL, "-M", "test-tiny-llama.npz", "-pt", "1,4,5,8",
         "-b", "2", "--prompt-len", "6", "--new-tokens", "5"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "tok/s" in proc.stdout
    # baseline continuation for the DCN comparison below (same args)
    want = [l for l in proc.stdout.splitlines() if "continuation" in l]
    assert want
    # the runtime drivers treat llama as any token model (host + spmd)
    for comm in ("host", "spmd"):
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "runtime.py"), "0", "2",
             "--platform", "cpu", "-m", MODEL, "-M", "test-tiny-llama.npz",
             "-pt", "1,4,5,8", "-b", "4", "-u", "2", "-c", comm],
            capture_output=True, env=env, cwd=str(tmp_path), text=True,
            timeout=300)
        assert proc.returncode == 0, proc.stderr
        assert "latency_sec=" in proc.stdout, (comm, proc.stdout)
    # DCN decode fleet (2 OS processes over TCP) == the local 2-stage
    # pipeline (the `want` baseline above), token for token — the family
    # dispatch covers the wire mode
    from test_dcn_runtime import _run_fleet
    opts = ["-m", MODEL, "-M", "test-tiny-llama.npz", "-pt", "1,4,5,8",
            "-b", "2", "--prompt-len", "6", "--new-tokens", "5"]
    data, _, _ = _run_fleet(
        tmp_path, opts, world=2,
        env_extra={"JAX_PLATFORMS": "cpu", "DCN_CONNECT_TIMEOUT": "20"},
        script="tools/generate.py",
        rank_argv=lambda rank, world: ["--rank", str(rank)])
    assert data.returncode == 0, data.stdout + data.stderr
    got = [l for l in data.stdout.splitlines() if "continuation" in l]
    assert got == want, (got, want)


@pytest.fixture(scope="module")
def mistral_setup():
    """Tiny Mistral: the llama block + sliding-window attention (window=4
    < prompt lengths used, so the mask is genuinely exercised)."""
    from transformers import MistralConfig, MistralForCausalLM
    cfg = get_model_config("pipeedge/test-tiny-mistral")
    hf_cfg = MistralConfig(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.kv_heads,
        intermediate_size=cfg.intermediate_size, vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.layer_norm_eps, rope_theta=cfg.rope_theta,
        sliding_window=cfg.sliding_window, tie_word_embeddings=False,
        attn_implementation="eager")
    torch.manual_seed(13)
    model = MistralForCausalLM(hf_cfg).eval()
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    return cfg, weights, model


def test_mistral_forward_matches_hf(mistral_setup):
    """Sliding-window attention (Mistral): forward logits == HF with the
    window (4) well inside the sequence (9) — positions attend only to
    the last 4, so a full-causal mask would diverge."""
    cfg, weights, model = mistral_setup
    assert cfg.sliding_window == 4
    total = 4 * cfg.num_hidden_layers
    sc = ShardConfig(1, total, is_first=True, is_last=True)
    params = llama_mod.load_params(cfg, sc, weights)
    fn = make_shard_fn(llama_mod.FAMILY, cfg, sc)
    ids = np.random.default_rng(29).integers(0, cfg.vocab_size, size=(2, 9))
    got = np.asarray(fn(params, jnp.asarray(ids, jnp.int32)))
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).logits.numpy()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_mistral_greedy_decode_matches_hf_generate(mistral_setup):
    """KV-cache decode honors the sliding window at every step (absolute
    q_pos anchors the window over the masked cache) — tokens match HF
    generate across a 2-stage partition, with prompt+new tokens well past
    the window."""
    cfg, weights, model = mistral_setup
    partition = [(1, 4), (5, 8)]
    total = 4 * cfg.num_hidden_layers
    sp = [llama_mod.load_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == total), weights)
        for l, r in partition]
    pipe = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition, sp,
                                 max_len=32)
    ids = np.random.default_rng(31).integers(0, cfg.vocab_size, size=(2, 7))
    got = np.asarray(pipe.generate(ids, new_tokens=8))
    with torch.no_grad():
        want = model.generate(torch.from_numpy(ids), max_new_tokens=8,
                              do_sample=False, pad_token_id=0).numpy()
    np.testing.assert_array_equal(got, want)
    # tp decode applies the same window over the head-sharded cache
    from jax.sharding import Mesh
    tp_pipe = decode.DecodePipeline(
        llama_mod.FAMILY, cfg, partition, sp, max_len=32,
        mesh=Mesh(np.asarray(jax.devices()[:2]), ("tp",)))
    np.testing.assert_array_equal(
        np.asarray(tp_pipe.generate(ids, new_tokens=8)), got)
    # sp prefill binds the window into the ring core (global-position
    # anchored masks; out-of-window K/V blocks skipped) — token-identical
    # to the non-sp pipeline, which itself matched HF generate above
    for kind in ("ring", "ulysses"):
        sp_pipe = decode.DecodePipeline(
            llama_mod.FAMILY, cfg, partition, sp, max_len=32,
            sp_mesh=Mesh(np.asarray(jax.devices()[:2]), ("sp",)),
            sp_kind=kind)
        sp_got = np.asarray(sp_pipe.generate(ids[:, :6], new_tokens=8))
        want6 = np.asarray(pipe.generate(ids[:, :6], new_tokens=8))
        np.testing.assert_array_equal(sp_got, want6)


@pytest.mark.slow
def test_mistral_sp_prefill_long_prompt(mistral_setup):
    """Long-prompt windowed sp prefill: prompt length (16) is 4x the
    sliding window (4) over a 4-chip sp mesh (chunk=4), so whole K/V
    blocks fall outside every window (_ring_steps(4, 4, 4) == 2 of 4)
    and the ring must still be token-identical to the plain pipeline."""
    from pipeedge_tpu.parallel.sequence import _ring_steps
    cfg, weights, _ = mistral_setup
    assert _ring_steps(4, 4, cfg.sliding_window) == 2
    total = 4 * cfg.num_hidden_layers
    sp = [llama_mod.load_params(
        cfg, ShardConfig(1, total, is_first=True, is_last=True), weights)]
    pipe = decode.DecodePipeline(llama_mod.FAMILY, cfg, [(1, total)], sp,
                                 max_len=32)
    ids = np.random.default_rng(37).integers(0, cfg.vocab_size, size=(2, 16))
    want = np.asarray(pipe.generate(ids, new_tokens=6))
    from jax.sharding import Mesh
    sp_pipe = decode.DecodePipeline(
        llama_mod.FAMILY, cfg, [(1, total)], sp, max_len=32,
        sp_mesh=Mesh(np.asarray(jax.devices()[:4]), ("sp",)))
    got = np.asarray(sp_pipe.generate(ids, new_tokens=6))
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow
def test_mistral_bucketed_attend_matches_full(mistral_setup):
    """Bucketed decode (static attend windows) composes with the llama
    family's cached step AND the sliding-window mask: tokens match the
    full-window pipeline across bucket boundaries."""
    cfg, weights, _ = mistral_setup
    partition = [(1, 4), (5, 8)]
    total = 4 * cfg.num_hidden_layers
    sp = [llama_mod.load_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == total), weights)
        for l, r in partition]
    ids = np.random.default_rng(41).integers(0, cfg.vocab_size, size=(2, 5))
    full = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition, sp,
                                 max_len=32, attend_floor=32)
    bucketed = decode.DecodePipeline(llama_mod.FAMILY, cfg, partition, sp,
                                     max_len=32, attend_floor=4)
    want = np.asarray(full.generate(ids, new_tokens=20))
    np.testing.assert_array_equal(
        np.asarray(bucketed.generate(ids, new_tokens=20)), want)
    # tp decode buckets through the family's tp_cached_block_step: the
    # GQA cache slice + window mask anchor over the truncated window
    from jax.sharding import Mesh
    tp_bucketed = decode.DecodePipeline(
        llama_mod.FAMILY, cfg, partition, sp, max_len=32, attend_floor=4,
        mesh=Mesh(np.asarray(jax.devices()[:2]), ("tp",)))
    np.testing.assert_array_equal(
        np.asarray(tp_bucketed.generate(ids, new_tokens=20)), want)
