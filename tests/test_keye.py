"""The keye family (models/keye.py: top-k experts without drops, a learned
attention selection with its own cache leaf, prefill in spans) against the
benchmark's plain reference, on the CPU at `pipeedge/test-tiny-keye`, with
seeded weights in the published key scheme."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_keye, weights
from benchmark.reference import keye_vl2 as reference
from pipeedge_tpu.models import (decoder, keye, llama, registry,
                                 stage_cache)
from pipeedge_tpu.models.layers import rope_rotate
from pipeedge_tpu.parallel import decode, expert
from pipeedge_tpu.telemetry import metrics as prom
from pipeedge_tpu.utils import jax_compat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "pipeedge/test-tiny-keye"
LENGTH = 30


def _config(tiny=True):
    name = "keye-vl-2.0-30b-a3b.json"
    with open(os.path.join(REPO, "benchmark", "configs", name)) as file:
        config = json.load(file)
    if tiny:
        with open(os.path.join(REPO, "tests", "benchmark_checks", "tiny",
                               "configs", name)) as file:
            config.update(json.load(file))
    return config


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """(config, weights file, pipeline, ids [2, 30], reference logits)."""
    config = _config()
    path = weights.write(config, 2 ** 31 + 7, str(
        tmp_path_factory.mktemp("keye") / "weights.npz"))
    pipe = decode.build_decode_pipeline(TINY, None, max_len=32,
                                        dtype=jnp.float32, model_file=path)
    ids = np.random.default_rng(3).integers(0, 100, size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)
    return config, path, pipe, ids, wanted


# the tiny model keeps 4 positions and prefills in spans of 8
@pytest.mark.parametrize("prompt_len", [3, 8, 13, 24])
def test_spans_then_decode_match_the_reference(prompt_len, tiny):
    _, _, pipe, ids, wanted = tiny
    data, caches = pipe._prefill(jnp.asarray(ids[:, :prompt_len], jnp.int32))
    assert data.shape[1] == 1       # the head saw the last row only
    got = [np.asarray(data[:, -1])]
    for pos in range(prompt_len, LENGTH):
        data, caches = pipe.extend(ids[:, pos:pos + 1], caches, pos)
        got.append(np.asarray(data[:, 0]))
    wanted = wanted[:, prompt_len - 1:]
    spread = wanted.max() - wanted.min()
    assert np.abs(np.stack(got, 1) - wanted).max() <= 1e-5 * spread


def test_queries_in_chunks_match_the_reference(tiny, monkeypatch):
    """A span whose scores would pass `decoder.SCORE_BYTES` runs its queries in
    chunks (at real sizes, always): two queries a chunk here."""
    config, path, _, ids, wanted = tiny
    monkeypatch.setattr(decoder, "SCORE_BYTES", 2 * 2 * 2 * 40 * 4)
    pipe = decode.build_decode_pipeline(TINY, None, max_len=32,
                                        dtype=jnp.float32, model_file=path)
    data, _ = pipe._prefill(jnp.asarray(ids[:, :24], jnp.int32))
    spread = wanted[:, 23].max() - wanted[:, 23].min()
    assert np.abs(np.asarray(data[:, -1]) - wanted[:, 23]).max() \
        <= 1e-5 * spread


def test_bfloat16_weights_are_computed_on_in_float32(tiny):
    """The seeded values are ones a bfloat16 holds, so the program's
    bfloat16 weights are the reference's float32 ones, and its float32
    activations over them (`exact_dot`) give the reference's logits."""
    _, path, _, ids, wanted = tiny
    pipe = decode.build_decode_pipeline(TINY, None, max_len=32,
                                        dtype=jnp.bfloat16, model_file=path)
    assert pipe.stages[0]["params"]["blocks"]["q"]["w"].dtype == jnp.bfloat16
    data, caches = pipe._prefill(jnp.asarray(ids[:, :24], jnp.int32))
    assert data.dtype == caches[0]["k"].dtype == jnp.float32
    got = [np.asarray(data[:, -1])]
    for pos in range(24, LENGTH):
        data, caches = pipe.extend(ids[:, pos:pos + 1], caches, pos)
        got.append(np.asarray(data[:, 0]))
    wanted = wanted[:, 23:]
    spread = wanted.max() - wanted.min()
    assert np.abs(np.stack(got, 1) - wanted).max() <= 1e-5 * spread


def test_exact_dot_over_bfloat16_weights():
    from pipeedge_tpu.models.layers import exact_dot
    rng = np.random.default_rng(5)
    x = rng.normal(size=(7, 300)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=(300, 11)), jnp.bfloat16)
    wanted = x.astype(np.float64) @ np.asarray(w, np.float64)
    got = np.asarray(exact_dot(jnp.asarray(x), w), np.float64)
    assert np.abs(got - wanted).max() <= 1e-5 * np.abs(wanted).max()
    rounded = np.asarray(jnp.asarray(x, jnp.bfloat16), np.float64) \
        @ np.asarray(w, np.float64)
    assert np.abs(rounded - wanted).max() > 1e-3 * np.abs(wanted).max()
    # the other layout, [out, in], as the experts are stored
    np.testing.assert_allclose(exact_dot(jnp.asarray(x), w.T, w_contract=1),
                               got, rtol=1e-6)


def test_spans_equal_one_whole_prompt_pass(tiny):
    _, _, pipe, ids, _ = tiny
    prompt = jnp.asarray(ids[:, :24], jnp.int32)
    in_spans, span_caches = pipe._prefill(prompt)
    whole, caches = pipe.extend(prompt, pipe._fresh_caches(2), 0)
    np.testing.assert_allclose(in_spans[:, -1], whole[:, -1], atol=1e-6)
    for name in ("k", "v", "ik"):
        np.testing.assert_allclose(span_caches[0][name], caches[0][name],
                                   atol=1e-6)
    # and the whole-prompt prefill program, which reads no cache
    stage = pipe.stages[0]
    alone, _ = stage["prefill"](stage["params"], prompt,
                                pipe._fresh_caches(2)[0])
    np.testing.assert_allclose(alone[:, -1], whole[:, -1], atol=1e-6)


def test_attention_that_keeps_every_key_is_llamas():
    cfg = dataclasses.replace(registry.get_model_config(TINY), index_topk=64)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 12, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 12, 2, 16)), jnp.float32)
            for _ in range(2))
    iq = jnp.zeros((2, 12, 2, 8))
    at = jnp.arange(12)
    got, scored, kept, fused = keye.sparse_attention(
        q, iq, jnp.zeros((2, 12, 2)), at,
        [(tuple(k[:, :, g] for g in range(2)),
          tuple(v[:, :, g] for g in range(2)), jnp.zeros((2, 12, 8)), at,
          None)], cfg)
    np.testing.assert_allclose(got, llama._gqa_attend(q, k, v, cfg),
                               atol=1e-6)
    assert int(scored) == int(kept) == 2 * 12 * 13 // 2
    assert int(fused) == 0          # the CPU runs no Mosaic: the einsums


@pytest.mark.parametrize("rows", ["equal", "unequal"])
def test_mrope(rows):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 9, 4, 16)), jnp.float32)
    pos = jnp.arange(5, 14)
    if rows == "equal":
        pos3 = jnp.stack([pos] * 3)
        np.testing.assert_array_equal(
            keye.mrope_rotate(x, pos3, 1e7, (2, 3, 3)),
            rope_rotate(x, pos, 1e7))
    else:
        pos3 = jnp.stack([pos, pos % 3, pos // 3])
    wanted = jnp.stack([reference.rotate(
        row, reference.mrope_angles(np.asarray(pos3), 16, 1e7, [2, 3, 3]))
        for row in x])
    np.testing.assert_allclose(keye.mrope_rotate(x, pos3, 1e7, (2, 3, 3)),
                               wanted, atol=1e-6)


def _expert_layer():
    cfg = registry.get_model_config(TINY)
    rng = np.random.default_rng(2)
    d, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_experts

    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, size=shape), jnp.float32)

    params = {"router": {"w": mat(d, e)},
              "experts": {"gate": mat(e, f, d), "up": mat(e, f, d),
                          "down": mat(e, d, f)}}
    return cfg, params, mat(2, 5, d)


def _plain_delta(cfg, params, x):
    """The layer as the reference has it: each chosen expert in turn."""
    tokens = x.reshape(-1, x.shape[-1])
    experts, gates = reference.route(tokens, params["router"]["w"].T,
                                     cfg.num_experts_per_tok)
    out = np.zeros(tokens.shape, np.float32)
    for t in range(tokens.shape[0]):
        for e, gate in zip(np.asarray(experts[t]), np.asarray(gates[t])):
            out[t] += gate * np.asarray(reference._expert(
                tokens[t:t + 1], *(params["experts"][name][e]
                                   for name in ("gate", "up", "down"))))[0]
    return out.reshape(x.shape)


@pytest.mark.parametrize("how", ["held", "ep"])
def test_four_shares_of_two_experts_add_up_to_the_layer(how):
    cfg, params, x = _expert_layer()
    whole, stats = expert.topk_ffn_delta(params, x, cfg)
    np.testing.assert_allclose(whole, _plain_delta(cfg, params, x),
                               atol=1e-5)
    assert stats[0] == 2 * 5 * cfg.num_experts_per_tok
    if how == "held":
        parts = []
        for first in range(0, cfg.n_experts, 2):
            mine = {"router": params["router"], "experts": {
                name: leaf[first:first + 2]
                for name, leaf in params["experts"].items()}}
            delta, share = expert.topk_ffn_delta(mine, x, cfg,
                                                 held=(first, 2))
            parts.append((delta, share))
        total = sum(delta for delta, _ in parts)
        assigned = sum(float(share[0]) for _, share in parts)
    else:
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
        specs = {"router": {"w": P()},
                 "experts": {name: P("ep") for name in params["experts"]}}
        total, share = jax.jit(jax_compat.shard_map(
            lambda p, y: expert.ep_topk_ffn_delta(p, y, cfg, "ep"),
            mesh=mesh, in_specs=(specs, P()), out_specs=(P(), P())))(
                params, x)
        assigned = float(share[0])
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert assigned == float(stats[0])


def _counters():
    return {(name, phase): prom.REGISTRY.counter(
        f"pipeedge_{name}_total", "").value(phase=phase)
        for name in keye.STATS for phase in ("prefill", "decode")}


def test_counters_of_one_batch_are_what_its_sizes_predict(tiny):
    _, _, pipe, ids, _ = tiny
    before = _counters()
    pipe.generate(ids[:, :24], 8)
    gained = {key: value - before[key]
              for key, value in _counters().items()}
    rows, layers, per_tok, topk = 2, 2, 2, 4
    spans, steps = 3, 7
    assert gained["moe_assignments", "prefill"] == rows * 24 * per_tok * layers
    assert gained["moe_assignments", "decode"] == rows * steps * per_tok \
        * layers
    assert gained["moe_layer_calls", "prefill"] == spans * layers
    assert gained["moe_layer_calls", "decode"] == steps * layers
    assert gained["sparse_scored", "prefill"] == rows * layers * 24 * 25 // 2
    assert gained["sparse_kept", "prefill"] == rows * layers \
        * costs_keye.kept_positions(_config(), 24)
    assert gained["sparse_scored", "decode"] == rows * layers \
        * sum(range(25, 32))
    assert gained["sparse_kept", "decode"] == rows * layers * steps * topk
    # the CPU keeps the einsums (`decoder.attend_masked`)
    assert gained["attend_fused_calls", "prefill"] == 0
    assert gained["attend_fused_calls", "decode"] == 0
    experts = _config()["num_experts"]
    for phase, tokens in (("prefill", rows * 8), ("decode", rows)):
        # a prefill in spans of 8: every touched expert's group is one tile
        # of the rule's at these sizes
        tile = expert.expert_tile(tokens, per_tok, experts)
        assert tile == -(-tokens // 8) * 8
        assert gained["moe_rows_computed", phase] \
            == tile * gained["moe_experts_touched", phase]
        assert gained["moe_experts_touched", phase] \
            <= 8 * gained["moe_layer_calls", phase]


def test_a_count_passes_two_to_the_31():
    cache = {stage_cache.STATS: jnp.zeros((2, 3, 2), jnp.int32)}
    step = jnp.full((2, 3), 2 ** 30 + 5, jnp.int32)
    for _ in range(5):
        cache = stage_cache.write_rows(cache, {stage_cache.STATS: step}, 0)
    assert stage_cache.read_stats(cache).tolist() == [10 * (2 ** 30 + 5)] * 3


def test_a_depth_cut_is_the_first_blocks_and_the_same_head(tiny):
    _, path, _, _, _ = tiny
    cut = registry.get_model_entry(TINY + "@1")
    assert cut.layers == 4 and cut.config.num_hidden_layers == 1
    assert registry.get_model_layers(TINY + "@1") == 4
    assert registry.get_model_default_weights_file(TINY + "@1") \
        == "test-tiny-keye@1.npz"
    assert dataclasses.replace(cut.config, num_hidden_layers=2) \
        == registry.get_model_config(TINY)
    _, whole, _ = registry.module_shard_factory(TINY, path, 1, 8,
                                                unroll=False)
    _, first, _ = registry.module_shard_factory(TINY + "@1", path, 1, 4,
                                                unroll=False)
    for part in ("embeddings", "final"):
        jax.tree_util.tree_map(np.testing.assert_array_equal, whole[part],
                               first[part])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a[:1], b),
        whole["blocks"], first["blocks"])
    with pytest.raises(ValueError, match="has 2 blocks"):
        registry.get_model_entry(TINY + "@3")


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_costs_against_hand_counts(size):
    config = _config(tiny=size == "tiny")
    if size == "tiny":
        # q, o: 32 x 64 each; k, v: 32 x 32; indexer 32 x (16 + 8 + 2);
        # router 32 x 8; an expert 3 x 32 x 16
        assert costs_keye.layer_dense_params(config) == 2 * 2048 + 2 * 1024 \
            + 832 + 256
        assert costs_keye.expert_params(config) == 1536
        assert costs_keye.kept_positions(config, 24) == 10 + 20 * 4
        assert costs_keye.prefill_flops(config, 2, 24) == 2 * (
            24 * 2 * 2 * (7232 + 2 * 1536)
            + 2 * (4 * 4 * 16 * 90 + 2 * 2 * 8 * 300) + 2 * 32 * 100)
        assert costs_keye.decode_step_bytes(config, 2, 25, 3.5) == 2 * (
            2 * (7232 + 3.5 * 1536 + 2 * 25 * 8 + 2 * 4 * 2 * 32)
            + 32 * 100)
        return
    # ISSUE 27's arithmetic: 18.87 M + 2.26 M + 0.26 M dense and 4.72 M an
    # expert a layer; 709.6 MFLOP of products a token; 1.27e14 FLOP a
    # prefill of 8 x 15,872; 4.05 GB a step at 50.5 distinct experts
    assert costs_keye.layer_dense_params(config) == 18874368 + 2260992 \
        + 262144
    assert costs_keye.expert_params(config) == 4718592
    assert costs_keye.token_product_flops(config) \
        == 2 * 6 * (21397504 + 8 * 4718592)        # 709.75 MFLOP
    assert costs_keye.kept_positions(config, 15872) \
        == 2048 * 2049 // 2 + 13824 * 2048
    assert 1.26e14 < costs_keye.prefill_flops(config, 8, 15872) < 1.28e14
    assert 4.0e9 < costs_keye.decode_step_bytes(config, 8, 16128, 50.5) \
        < 4.1e9


@pytest.mark.parametrize("case", ["spread", "ties", "few", "negative"])
def test_topk_mask_is_the_sorted_top_k(case):
    rng = np.random.default_rng(4)
    score = rng.normal(size=(3, 5, 40)).astype(np.float32)
    valid = rng.random(size=(1, 5, 40)) < 0.8
    if case == "ties":          # many equal scores at the k-th value
        score = np.round(score)
    elif case == "few":         # fewer valid than k
        valid = rng.random(size=(1, 5, 40)) < 0.1
    elif case == "negative":
        score = -np.abs(score) - 1.0
    got = np.asarray(keye._topk_mask(jnp.asarray(score), jnp.asarray(valid),
                                     7))
    live = np.broadcast_to(valid, score.shape)
    for row, ok, mask in zip(score.reshape(-1, 40), live.reshape(-1, 40),
                             got.reshape(-1, 40)):
        order = sorted(np.flatnonzero(ok), key=lambda i: (-row[i], i))[:7]
        assert sorted(np.flatnonzero(mask)) == sorted(order)


@pytest.mark.parametrize("asked", ["mesh", "sp_mesh", "ep_mesh",
                                   "tp_ep_mesh", "cache_bits"])
def test_what_the_family_cannot_do_is_refused_by_name(asked):
    from jax.sharding import Mesh
    entry = registry.get_model_entry(TINY)
    _, params, _ = registry.module_shard_factory(TINY, None, 1, 8,
                                                 unroll=False)
    axes = {"mesh": ("tp",), "sp_mesh": ("sp",), "ep_mesh": ("ep",),
            "tp_ep_mesh": ("tp", "ep")}
    if asked == "cache_bits":
        option, wanted = {"cache_bits": 8}, "int8 cache route"
    else:
        shape = (2,) * len(axes[asked])
        option = {asked: Mesh(np.array(jax.devices()[:2 ** len(shape)])
                              .reshape(shape), axes[asked])}
        wanted = {"mesh": "tp_cached_block_step",
                  "sp_mesh": "sp_prefill_block_step"}.get(
                      asked, "ep_cached_block_step")
    with pytest.raises(NotImplementedError, match=wanted):
        decode.DecodePipeline(entry.family.FAMILY, entry.config, [(1, 8)],
                              [params], max_len=32, **option)
