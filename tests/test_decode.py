"""KV-cache pipelined decoding vs HF greedy generation (GPT-2 family)."""
import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax.numpy as jnp  # noqa: E402

from pipeedge_tpu.models import ShardConfig, stage_cache  # noqa: E402
from pipeedge_tpu.models import gpt2 as gpt2_mod  # noqa: E402
from pipeedge_tpu.models.layers import TransformerConfig  # noqa: E402
from pipeedge_tpu.parallel import decode  # noqa: E402

TINY = dict(hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
            intermediate_size=64)


@pytest.fixture(scope="module")
def gpt2_setup():
    from transformers import GPT2Config, GPT2LMHeadModel
    hf_cfg = GPT2Config(n_embd=32, n_layer=3, n_head=4, n_inner=64,
                        vocab_size=100, n_positions=64)
    torch.manual_seed(7)
    model = GPT2LMHeadModel(hf_cfg).eval()
    cfg = TransformerConfig(model_type="gpt2", **TINY, layer_norm_eps=1e-5,
                            vocab_size=100, max_position_embeddings=64)
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    return cfg, weights, model


def _stage_params(cfg, partition, weights):
    total = 4 * cfg.num_hidden_layers
    return [gpt2_mod.load_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == total), weights)
        for l, r in partition]


def _beam_oracle(cfg, weights, prompt, beams, steps):
    """Step-by-step numpy beam search for one prompt [S]: a full (no-cache)
    forward per hypothesis, exactly `generate_beam`'s semantics."""
    total = 4 * cfg.num_hidden_layers
    sc = ShardConfig(1, total, is_first=True, is_last=True)
    params = gpt2_mod.load_params(cfg, sc, weights)
    from pipeedge_tpu.models.shard import make_shard_fn
    fn = make_shard_fn(gpt2_mod.FAMILY, cfg, sc)

    def logprobs(seqs):   # [N, S] -> [N, V] next-token log-probs
        logits = np.asarray(fn(params, jnp.asarray(seqs, jnp.int32)))
        x = logits[:, -1].astype(np.float64)
        x = x - x.max(axis=-1, keepdims=True)
        return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))

    lp = logprobs(prompt[None])[0]
    hyps = [(lp[t], [int(t)]) for t in np.argsort(-lp)[:beams]]
    for _ in range(steps - 1):
        seqs = np.stack([np.concatenate([prompt, h[1]]) for h in hyps])
        lps = logprobs(seqs)
        cand = [(h[0] + lps[i][t], h[1] + [int(t)])
                for i, h in enumerate(hyps) for t in range(cfg.vocab_size)]
        cand.sort(key=lambda c: -c[0])
        hyps = cand[:beams]
    return np.asarray(hyps[0][1])


@pytest.mark.parametrize("partition", [
    [(1, 12)],
    [(1, 4), (5, 12)],
    [(1, 4), (5, 8), (9, 12)],
])
@pytest.mark.slow
def test_greedy_matches_hf_generate(gpt2_setup, partition):
    """Pipelined KV-cache greedy decode == HF generate(do_sample=False),
    token for token, for 1..3 stage partitions."""
    cfg, weights, model = gpt2_setup
    pipe = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, partition,
        _stage_params(cfg, partition, weights), max_len=32)
    ids = np.asarray(
        np.random.default_rng(21).integers(0, 100, size=(3, 7)), np.int64)
    got = np.asarray(pipe.generate(ids, new_tokens=8))
    with torch.no_grad():
        expected = model.generate(
            torch.from_numpy(ids), max_new_tokens=8, do_sample=False,
            pad_token_id=0).numpy()
    np.testing.assert_array_equal(got, expected)


def test_decode_matches_teacher_forcing(gpt2_setup):
    """Step-by-step cached logits == full-sequence forward logits."""
    cfg, weights, _ = gpt2_setup
    total = 4 * cfg.num_hidden_layers
    sc = ShardConfig(1, total, is_first=True, is_last=True)
    params = gpt2_mod.load_params(cfg, sc, weights)
    pre, dec = decode.make_stage_fns(gpt2_mod.FAMILY, cfg, sc)
    ids = jnp.asarray(
        np.random.default_rng(5).integers(0, 100, size=(2, 10)), jnp.int32)
    cache = stage_cache.init_cache(cfg, cfg.num_hidden_layers, 2, 16)
    params = dict(params)
    params["blocks"] = decode.stage_blocks(params)

    from pipeedge_tpu.models.shard import make_shard_fn
    full = np.asarray(make_shard_fn(gpt2_mod.FAMILY, cfg, sc)(params,
                                                              ids))
    got, cache = pre(params, ids[:, :6], cache)
    np.testing.assert_allclose(np.asarray(got), full[:, :6], rtol=2e-5,
                               atol=2e-5)
    for t in range(6, 10):
        got, cache = dec(params, ids[:, t:t + 1], cache, t)
        np.testing.assert_allclose(np.asarray(got)[:, 0], full[:, t],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_int8_kv_cache_close_to_exact(gpt2_setup):
    """int8-quantized KV cache (QuantPipe idea applied to decode): cached
    step logits stay close to the exact full-sequence forward."""
    cfg, weights, _ = gpt2_setup
    total = 4 * cfg.num_hidden_layers
    sc = ShardConfig(1, total, is_first=True, is_last=True)
    params = dict(gpt2_mod.load_params(cfg, sc, weights))
    params["blocks"] = decode.stage_blocks(params)
    pre, dec = decode.make_stage_fns(gpt2_mod.FAMILY, cfg, sc)
    ids = jnp.asarray(
        np.random.default_rng(6).integers(0, 100, size=(2, 10)), jnp.int32)
    cache = stage_cache.init_cache(cfg, cfg.num_hidden_layers, 2, 16, cache_bits=8)
    assert cache["k"].dtype == jnp.int8

    from pipeedge_tpu.models.shard import make_shard_fn
    full = np.asarray(make_shard_fn(gpt2_mod.FAMILY, cfg, sc)(params, ids))
    got, cache = pre(params, ids[:, :6], cache)
    np.testing.assert_allclose(np.asarray(got), full[:, :6], rtol=0.1,
                               atol=0.05)
    for t in range(6, 10):
        got, cache = dec(params, ids[:, t:t + 1], cache, t)
        np.testing.assert_allclose(np.asarray(got)[:, 0], full[:, t],
                                   rtol=0.1, atol=0.05)

    with pytest.raises(ValueError, match="cache_bits"):
        stage_cache.init_cache(cfg, 2, 1, 8, cache_bits=4)


@pytest.mark.slow
def test_sampling_and_step_callback(gpt2_setup):
    """Temperature sampling: deterministic per seed, varies across seeds,
    stays in-vocab; temperature=0 equals greedy; callback fires per step."""
    cfg, weights, _ = gpt2_setup
    partition = [(1, 12)]
    pipe = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, partition,
        _stage_params(cfg, partition, weights), max_len=32)
    ids = np.asarray(
        np.random.default_rng(41).integers(0, 100, size=(2, 6)), np.int64)
    steps = []
    greedy = np.asarray(pipe.generate(
        ids, 8, temperature=0.0, step_callback=lambda s, t: steps.append(s)))
    assert steps == list(range(8))
    greedy2 = np.asarray(pipe.generate(ids, 8))
    np.testing.assert_array_equal(greedy, greedy2)
    s_a = np.asarray(pipe.generate(ids, 8, temperature=0.9, seed=1))
    s_a2 = np.asarray(pipe.generate(ids, 8, temperature=0.9, seed=1))
    s_b = np.asarray(pipe.generate(ids, 8, temperature=0.9, seed=2))
    np.testing.assert_array_equal(s_a, s_a2)
    assert not np.array_equal(s_a, s_b)
    assert s_a[:, 6:].min() >= 0 and s_a[:, 6:].max() < 100
    # top-k=1 collapses sampling to greedy regardless of temperature
    top1 = np.asarray(pipe.generate(ids, 8, temperature=0.9, top_k=1, seed=3))
    np.testing.assert_array_equal(top1, greedy)


@pytest.mark.slow
def test_beam_search_matches_oracle(gpt2_setup):
    """generate_beam == a step-by-step numpy beam search over full
    (no-cache) forward log-probs; beams=1 degenerates to greedy."""
    cfg, weights, _ = gpt2_setup
    partition = [(1, 4), (5, 12)]
    pipe = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, partition,
        _stage_params(cfg, partition, weights), max_len=32)
    ids = np.asarray(
        np.random.default_rng(51).integers(0, 100, size=(2, 6)), np.int64)

    got1 = np.asarray(pipe.generate_beam(ids, 6, beams=1))
    np.testing.assert_array_equal(got1, np.asarray(pipe.generate(ids, 6)))

    beams, steps = 3, 4
    got = np.asarray(pipe.generate_beam(ids, steps, beams=beams))

    for b in range(ids.shape[0]):
        np.testing.assert_array_equal(
            got[b, 6:], _beam_oracle(cfg, weights, ids[b], beams, steps))


@pytest.mark.slow
def test_tp_decode_matches_plain(gpt2_setup):
    """Megatron tensor-parallel decode (head-sharded KV cache, 2 psums per
    block under shard_map) generates the same tokens as the single-device
    pipeline."""
    import jax
    from jax.sharding import Mesh
    cfg, weights, _ = gpt2_setup
    ids = np.asarray(
        np.random.default_rng(31).integers(0, 100, size=(2, 6)), np.int64)
    for partition in ([(1, 12)], [(1, 8), (9, 12)]):
        sp = _stage_params(cfg, partition, weights)
        plain = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition, sp,
                                      max_len=24)
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        tp = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition, sp,
                                   max_len=24, mesh=mesh)
        got_plain = np.asarray(plain.generate(ids, 8))
        got_tp = np.asarray(tp.generate(ids, 8))
        np.testing.assert_array_equal(got_tp, got_plain)

    # int8 KV composes with tp: the per-(position, head) scale rows carry
    # a head axis and shard over 'tp' with the K/V buffers, and each
    # device quantizes its own head slice with the same per-head math as
    # the unsharded int8 path — tokens match the single-device int8 run
    sp1 = _stage_params(cfg, [(1, 12)], weights)
    int8_plain = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, [(1, 12)],
                                       sp1, max_len=24, cache_bits=8)
    int8_tp = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, [(1, 12)], sp1, max_len=24, cache_bits=8,
        mesh=Mesh(np.array(jax.devices()[:2]), ("tp",)))
    np.testing.assert_array_equal(
        np.asarray(int8_tp.generate(ids, 8)),
        np.asarray(int8_plain.generate(ids, 8)))


@pytest.mark.slow
def test_sp_prefill_matches_plain(gpt2_setup):
    """Sequence-parallel prefill (causal ring attention over an 'sp' mesh,
    K/V all-gathered into the caches) + plain decode steps == the
    single-device pipeline, token for token."""
    import jax
    from jax.sharding import Mesh
    cfg, weights, _ = gpt2_setup
    ids = np.asarray(
        np.random.default_rng(61).integers(0, 100, size=(2, 8)), np.int64)
    for partition in ([(1, 12)], [(1, 8), (9, 12)]):
        sp = _stage_params(cfg, partition, weights)
        plain = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition, sp,
                                      max_len=24)
        want = np.asarray(plain.generate(ids, 8))
        sp_mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        for kind in ("ring", "ulysses"):
            piped = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                          sp, max_len=24, sp_mesh=sp_mesh,
                                          sp_kind=kind)
            got = np.asarray(piped.generate(ids, 8))
            np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="not divisible by"):
        piped.generate(ids[:, :7], 4)
    with pytest.raises(ValueError, match="does not compose"):
        decode.DecodePipeline(gpt2_mod.FAMILY, cfg, [(1, 12)],
                              _stage_params(cfg, [(1, 12)], weights),
                              max_len=24, sp_mesh=sp_mesh, cache_bits=8)


@pytest.mark.fleet
def test_generate_cli(tmp_path):
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    for extra in ([], ["--kv-bits", "8"], ["--concurrent", "3"],
                  ["--beams", "2"]):
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "generate.py"),
             "-m", "pipeedge/test-tiny-gpt2", "-pt", "1,4,5,8", "-b", "2",
             "--prompt-len", "6", "--new-tokens", "5"] + extra,
            capture_output=True, env=env, cwd=str(tmp_path), text=True,
            timeout=300)
        assert proc.returncode == 0, proc.stderr
        assert "tok/s" in proc.stdout
        if extra[:1] == ["--concurrent"]:
            assert "continuous batching" in proc.stdout
        if extra[:1] == ["--beams"]:
            assert "beam 2" in proc.stdout   # CLI really ran beam search


@pytest.mark.fleet
def test_generate_dcn_matches_local(tmp_path):
    """Pipelined decoding across two OS processes over TCP produces the
    same greedy continuation as the local two-stage pipeline (shared
    weights file)."""
    import os
    import subprocess
    import sys

    from test_dcn_runtime import _run_fleet
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
               DCN_CONNECT_TIMEOUT="20")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "save_model_weights.py"),
         "-m", "pipeedge/test-tiny-gpt2", "--random"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr
    npz = str(tmp_path / "test-tiny-gpt2.npz")

    opts = ["-m", "pipeedge/test-tiny-gpt2", "-M", npz, "-pt", "1,4,5,8",
            "-b", "2", "--prompt-len", "6", "--new-tokens", "5"]
    local = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "generate.py")] + opts,
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert local.returncode == 0, local.stderr
    want = [l for l in local.stdout.splitlines() if "continuation" in l]
    assert want

    data, _, _ = _run_fleet(
        tmp_path, opts, world=2,
        env_extra={"JAX_PLATFORMS": "cpu", "DCN_CONNECT_TIMEOUT": "20"},
        script="tools/generate.py",
        rank_argv=lambda rank, world: ["--rank", str(rank)])
    assert data.returncode == 0, data.stdout + data.stderr
    got = [l for l in data.stdout.splitlines() if "continuation" in l]
    assert got == want, (got, want)
    assert "2 DCN ranks" in data.stdout

    # quantized stage edges (QuantPipe compression on the wire): the fleet
    # still decodes end-to-end (tokens may differ within quant error)
    data, _, _ = _run_fleet(
        tmp_path, opts + ["--edge-bits", "8"], world=2,
        env_extra={"JAX_PLATFORMS": "cpu", "DCN_CONNECT_TIMEOUT": "20",
                   "PIPEEDGE_NATIVE_QUANT": "0"},
        script="tools/generate.py",
        rank_argv=lambda rank, world: ["--rank", str(rank)])
    assert data.returncode == 0, data.stdout + data.stderr
    assert "2 DCN ranks" in data.stdout
    q_lines = [l for l in data.stdout.splitlines() if "continuation" in l]
    assert q_lines and q_lines[0].count(",") == 4  # 5 tokens emitted


@pytest.mark.fleet
def test_generate_dcn_adaptive_edge_quant(tmp_path):
    """The adaptive bitwidth policies steer decode DCN
    edges. ADAPTIVE_QUANT=HEURISTIC2 with an aggressive SEND_CONSTRAINT
    forces rank 0's output edge from raw (bit 0) down to the 2-bit floor
    after the first telemetry window; the consumer keeps decoding because
    the bitwidth rides the wire header (comm/wire.py), and the fleet still
    emits a full continuation."""
    import os
    import subprocess
    import sys

    from test_dcn_runtime import _run_fleet
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
               DCN_CONNECT_TIMEOUT="20")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "save_model_weights.py"),
         "-m", "pipeedge/test-tiny-gpt2", "--random"],
        capture_output=True, env=env, cwd=str(tmp_path), text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr
    npz = str(tmp_path / "test-tiny-gpt2.npz")

    opts = ["-m", "pipeedge/test-tiny-gpt2", "-M", npz, "-pt", "1,4,5,8",
            "-b", "2", "--prompt-len", "6", "--new-tokens", "10"]
    data, _, _ = _run_fleet(
        tmp_path, opts, world=2,
        env_extra={"JAX_PLATFORMS": "cpu", "DCN_CONNECT_TIMEOUT": "20",
                   "PIPEEDGE_NATIVE_QUANT": "0",
                   # tokens/sec target far beyond a local 2-stage fleet:
                   # HEURISTIC2's transfer budget ~0 -> 2-bit floor
                   "ADAPTIVE_QUANT": "HEURISTIC2",
                   "SEND_CONSTRAINT": "1e9", "WINDOW_SIZE": "4"},
        script="tools/generate.py",
        rank_argv=lambda rank, world: ["--rank", str(rank)])
    assert data.returncode == 0, data.stdout + data.stderr
    assert "2 DCN ranks" in data.stdout
    # rank 0 (the data rank here) owns the adapted edge; the policy logs
    # each window decision via the runtime logger
    assert "Adaptive quantization (HEURISTIC2): bitwidth=2" in (
        data.stdout + data.stderr)
    lines = [l for l in data.stdout.splitlines() if "continuation" in l]
    assert lines and lines[0].count(",") == 9      # 10 tokens emitted


@pytest.mark.slow
def test_chunked_prefill_matches_whole(gpt2_setup):
    """prefill_ubatch pipelines the prompt pass in batch chunks; tokens
    must match the unchunked run exactly (dense model: routing-free)."""
    cfg, weights, _ = gpt2_setup
    partition = [(1, 4), (5, 12)]
    pipe = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, partition,
        _stage_params(cfg, partition, weights), max_len=24)
    ids = np.asarray(
        np.random.default_rng(71).integers(0, 100, size=(4, 6)), np.int64)
    want = np.asarray(pipe.generate(ids, 7))
    got = np.asarray(pipe.generate(ids, 7, prefill_ubatch=2))
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="not divisible by"):
        pipe.generate(ids[:3], 4, prefill_ubatch=2)


def test_round_partition_to_blocks():
    """Sublayer-granular scheduler cuts round to block boundaries with
    coverage preserved (the profile->schedule->decode glue)."""
    r = decode.round_partition_to_blocks
    assert r([(1, 6), (7, 12)], 12) == [(1, 8), (9, 12)]
    assert r([(1, 5), (6, 7), (8, 12)], 12) == [(1, 4), (5, 8), (9, 12)]
    assert r([(1, 12)], 12) == [(1, 12)]
    # cuts collapsing onto the same boundary merge stages
    assert r([(1, 5), (6, 6), (7, 12)], 12) == [(1, 4), (5, 8), (9, 12)]
    assert r([(1, 1), (2, 2), (3, 12)], 12) == [(1, 4), (5, 12)]
    for part in (r([(1, 3), (4, 9), (10, 12)], 12),):
        covered = [x for l, rr in part for x in range(l, rr + 1)]
        assert covered == list(range(1, 13))
    with pytest.raises(ValueError, match="multiple of 4"):
        r([(1, 5)], 5)


def test_decode_validation_errors(gpt2_setup):
    cfg, weights, _ = gpt2_setup
    with pytest.raises(ValueError, match="block-aligned"):
        decode.make_stage_fns(gpt2_mod.FAMILY, cfg,
                              ShardConfig(1, 6, is_first=True, is_last=False))
    with pytest.raises(ValueError, match="contiguously cover"):
        decode.DecodePipeline(gpt2_mod.FAMILY, cfg, [(1, 4)],
                              _stage_params(cfg, [(1, 4)], weights),
                              max_len=8)
    with pytest.raises(ValueError, match="positions"):
        decode.DecodePipeline(gpt2_mod.FAMILY, cfg, [(1, 12)],
                              _stage_params(cfg, [(1, 12)], weights),
                              max_len=100)  # > max_position_embeddings=64
    partition = [(1, 12)]
    pipe = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, partition,
        _stage_params(cfg, partition, weights), max_len=8)
    with pytest.raises(ValueError, match="exceeds max_len"):
        pipe.generate(np.zeros((1, 6), np.int64), new_tokens=4)
    # new_tokens=0 honors the [B, S + new_tokens] contract
    ids = np.zeros((1, 4), np.int64)
    assert np.asarray(pipe.generate(ids, 0)).shape == (1, 4)


@pytest.mark.slow
def test_bucketed_attend_crosses_buckets(gpt2_setup):
    """Bucketed decode-step attention (attend_bucket: static power-of-2
    windows instead of max_len) is token-identical to the full-window
    pipeline while the generation crosses several bucket boundaries
    (floor 4 -> buckets 4, 8, 16, 32 over a 28-token run), for both the
    f32 and the int8 cache, with HF generate as the external oracle."""
    import torch

    from pipeedge_tpu.parallel.decode import attend_bucket

    assert [attend_bucket(p, 64, 4) for p in (1, 4, 5, 9, 17, 33)] == \
        [4, 4, 8, 16, 32, 64]
    with pytest.raises(ValueError, match="exceeds"):
        attend_bucket(65, 64, 4)

    cfg, weights, model = gpt2_setup
    ids = np.asarray(
        np.random.default_rng(71).integers(0, 100, size=(2, 5)), np.int64)
    new = 28
    partition = [(1, 8), (9, 12)]
    sp = _stage_params(cfg, partition, weights)
    full = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition, sp,
                                 max_len=64, attend_floor=64)
    bucketed = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition, sp,
                                     max_len=64, attend_floor=4)
    want = np.asarray(full.generate(ids, new))
    np.testing.assert_array_equal(np.asarray(bucketed.generate(ids, new)),
                                  want)
    with torch.no_grad():
        hf = model.generate(torch.from_numpy(ids), max_new_tokens=new,
                            do_sample=False, pad_token_id=0).numpy()
    np.testing.assert_array_equal(want, hf)

    int8_full = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition, sp,
                                      max_len=64, cache_bits=8,
                                      attend_floor=64)
    int8_bucketed = decode.DecodePipeline(gpt2_mod.FAMILY, cfg, partition,
                                          sp, max_len=64, cache_bits=8,
                                          attend_floor=4)
    np.testing.assert_array_equal(
        np.asarray(int8_bucketed.generate(ids, new)),
        np.asarray(int8_full.generate(ids, new)))

    # tensor-parallel stages bucket too (shard_map closure re-bound per
    # static window; the position axis is unsharded) — f32 AND int8,
    # whose [B, T, H] scale rows truncate on the same position axis
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    tp_bucketed = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, partition, sp, max_len=64, attend_floor=4,
        mesh=mesh)
    np.testing.assert_array_equal(np.asarray(tp_bucketed.generate(ids, new)),
                                  want)
    tp_int8_bucketed = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, partition, sp, max_len=64, attend_floor=4,
        cache_bits=8, mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(tp_int8_bucketed.generate(ids, new)),
        np.asarray(int8_full.generate(ids, new)))


def _long_pipe(max_len, seed, **kw):
    """A one-stage tiny GPT-2 with 512 positions and seeded random weights
    (the HF fixture has 64): room for a cache several attend buckets long."""
    cfg = TransformerConfig(model_type="gpt2", **TINY, layer_norm_eps=1e-5,
                            vocab_size=100, max_position_embeddings=512)
    sc = ShardConfig(1, 12, is_first=True, is_last=True)
    return decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, [(1, 12)],
        [gpt2_mod.init_params(cfg, sc, seed=seed)], max_len=max_len, **kw)


@pytest.mark.parametrize("tp", [False, True], ids=["plain", "tp"])
@pytest.mark.parametrize("cache_bits", [0, 8], ids=["fp", "int8"])
def test_decode_step_updates_cache_in_place(cache_bits, tp):
    """The compiled decode step of a stage holds ONE copy of its cache and
    moves none of it but the rows and the window: every cache leaf is
    aliased input to output, the donated input is dead after the call,
    and with `max_len` eight times the attend bucket the program's
    temporaries stay under a single layer's cache."""
    import re

    import jax
    from jax.sharding import Mesh
    pipe = _long_pipe(
        512, seed=3, cache_bits=cache_bits,
        mesh=Mesh(np.array(jax.devices()[:2]), ("tp",)) if tp else None)
    st = pipe.stages[0]
    batch, pos = 2, 40
    [cache] = pipe._fresh_caches(batch)
    tok = jnp.zeros((batch, 1), jnp.int32)
    assert pipe._read_len(pos) == 64
    compiled = st["decode"].lower(st["params"], tok, cache, pos,
                                  read_len=64).compile()

    header = compiled.as_text().split("\n", 1)[0]
    aliased = re.findall(r"\{\d+\}: \(\d+, \{\}, (?:may|must)-alias\)",
                         header)
    assert len(aliased) == len(cache), header
    on_device = sum(leaf.addressable_shards[0].data.nbytes
                    for leaf in cache.values())
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == on_device
    assert mem.temp_size_in_bytes < on_device // st["n_blocks"], mem

    _, new = pipe._decode_step(st, tok, cache, pos)
    assert all(leaf.is_deleted() for leaf in cache.values())
    assert not any(leaf.is_deleted() for leaf in new.values())


def test_prefix_handle_outlives_donated_steps(gpt2_setup):
    """A prefix handle keeps its caches across calls that donate theirs:
    at batch 1, where tiling the handle's rows repeats them once and must
    still copy, two generate calls off one handle both give the tokens of
    the whole prompt prefilled at once."""
    cfg, weights, _ = gpt2_setup
    partition = [(1, 4), (5, 12)]
    pipe = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, partition,
        _stage_params(cfg, partition, weights), max_len=32)
    ids = np.asarray(
        np.random.default_rng(61).integers(0, 100, size=(1, 9)), np.int64)
    want = np.asarray(pipe.generate(ids, 6))[:, 5:]
    handle = pipe.precompute_prefix(ids[0, :5])
    for _ in range(2):
        got = np.asarray(pipe.generate(ids[:, 5:], 6, prefix=handle))
        np.testing.assert_array_equal(got, want)
    assert not any(leaf.is_deleted() for c in handle["caches"]
                   for leaf in c.values())


def test_beam_reshuffle_outlives_donated_steps(gpt2_setup):
    """Beam search tiles the prefill's caches and regathers them by parent
    beam between steps that donate them; the tokens are the oracle's."""
    cfg, weights, _ = gpt2_setup
    pipe = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, [(1, 12)],
        _stage_params(cfg, [(1, 12)], weights), max_len=32)
    ids = np.asarray(
        np.random.default_rng(52).integers(0, 100, size=(1, 5)), np.int64)
    got = np.asarray(pipe.generate_beam(ids, 4, beams=2))
    np.testing.assert_array_equal(
        got[0, 5:], _beam_oracle(cfg, weights, ids[0], 2, 4))


@pytest.mark.parametrize(
    "heads, kv_heads, head_dim, span, window, dtype", [
        (4, 4, 64, 1, 0, "float32"),
        (4, 4, 64, 4, 0, "float32"),
        (4, 2, 64, 1, 0, "float32"),
        (4, 2, 64, 4, 5, "float32"),
        (8, 2, 16, 1, 6, "float32"),
        (4, 4, 64, 40, 0, "float32"),   # more columns than one MXU pass
        (8, 4, 64, 20, 3, "float32"),   # the same, grouped kv heads
        (2, 2, 128, 1, 0, "bfloat16"),
        (2, 1, 128, 4, 0, "bfloat16"),
        (4, 4, 64, 1, 6, "bfloat16"),
        (4, 4, 64, 4, 0, "bfloat16"),
    ])
def test_stored_form_attention_matches_plain_einsum(heads, kv_heads,
                                                    head_dim, span, window,
                                                    dtype):
    """A step over a cache in its stored form (`[L, B, T, H*Dh]`, the heads
    folded, the window read as stored and never reshaped) attends what the
    plain `[B, T, H, Dh]` einsum written here attends: rows at [pos, pos +
    span) over the positions below `pos` and, causally, themselves, grouped
    kv heads repeated up to the query heads, a sliding window where one is
    set; and the step's rows land at `pos` in the stored form, nothing
    written beside them."""
    dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(83)
    batch, max_len, read_len, pos, layer = 2, 64, 32, 21, 1
    cfg = TransformerConfig(model_type="llama", hidden_size=heads * head_dim,
                            num_hidden_layers=2, num_attention_heads=heads,
                            num_kv_heads=kv_heads,
                            intermediate_size=8)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    q = draw(batch, span, heads, head_dim)
    k_new, v_new = (draw(batch, span, kv_heads, head_dim) for _ in "kv")
    held = {t: draw(2, batch, max_len, kv_heads, head_dim) for t in "kv"}
    cache = stage_cache.init_cache(cfg, 2, batch, max_len, dtype)
    assert cache["k"].shape == (2, batch, max_len, kv_heads * head_dim)
    cache = {t: held[t].reshape(cache[t].shape) for t in "kv"}

    k, v, keep, bcache = stage_cache.cache_update_and_read(
        stage_cache.LayerCache(cache, layer), k_new, v_new, pos, False, span,
        dtype, read_len=read_len, window=window)
    assert k[0].shape == (batch, read_len, kv_heads * head_dim)
    got = np.asarray(stage_cache.attend(q, k, v, keep, cfg), np.float32)

    def f32(x):
        return np.asarray(x, np.float32)

    # the reference: every key in one [B, T, H, Dh] array, heads repeated
    keys, values = (np.repeat(np.concatenate(
        [f32(held[t][layer, :, :pos]), f32(new)], axis=1),
        heads // kv_heads, axis=2) for t, new in (("k", k_new), ("v", v_new)))
    scores = np.einsum("bqhd,bkhd->bhqk", f32(q), keys) / np.sqrt(head_dim)
    q_at = pos + np.arange(span)[:, None]
    k_at = np.arange(pos + span)[None]
    seen = k_at <= q_at
    if window:
        seen &= k_at > q_at - window
    scores = np.where(seen[None, None], scores, -1e30)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs = f32(jnp.asarray(probs / probs.sum(-1, keepdims=True), dtype))
    want = np.einsum("bhqk,bkhd->bqhd", probs, values).reshape(got.shape)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    rows = {t: jnp.stack([bcache.rows[t]] * 2) for t in "kv"}
    written = stage_cache.write_rows(cache, rows, pos)
    for t, new in (("k", k_new), ("v", v_new)):
        want = np.array(f32(cache[t]))
        want[:, :, pos:pos + span] = f32(new).reshape(batch, span, -1)
        np.testing.assert_array_equal(f32(written[t]), want)
