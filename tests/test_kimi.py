"""The kimi family (models/kimi.py: a latent cache with an expanded and an
absorbed attention, a dense layer and expert layers as runs of one stage, a
sigmoid router beside a shared expert, a chip's share of the experts and of
the vocabulary) against the benchmark's plain reference, on the CPU at
`pipeedge/test-tiny-kimi`, with seeded weights in the published key scheme."""
import collections
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_kimi, weights
from benchmark.reference import kimi_k2 as reference
from pipeedge_tpu.models import (ShardConfig, decoder, kimi, registry,
                                 stage_cache)
from pipeedge_tpu.models.shard import BlockRuns, shard_apply
from pipeedge_tpu.parallel import decode, expert
from pipeedge_tpu.telemetry import metrics as prom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "pipeedge/test-tiny-kimi"
LENGTH = 30


def _config(tiny=True, **over):
    name = "kimi-k2-instruct.json"
    with open(os.path.join(REPO, "benchmark", "configs", name)) as file:
        config = json.load(file)
    if tiny:
        with open(os.path.join(REPO, "tests", "benchmark_checks", "tiny",
                               "configs", name)) as file:
            config.update(json.load(file))
    config.update(over)
    return config


def _logits_through_the_cache(pipe, ids, prompt_len):
    data, caches = pipe._prefill(jnp.asarray(ids[:, :prompt_len], jnp.int32))
    assert data.shape[1] == 1       # the head saw the last row only
    got = [np.asarray(data[:, -1])]
    for pos in range(prompt_len, ids.shape[1]):
        data, caches = pipe.extend(ids[:, pos:pos + 1], caches, pos)
        got.append(np.asarray(data[:, 0]))
    return np.stack(got, 1)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The benchmark's tiny cut, a share of the model (experts 0-1 of 8,
    half the vocabulary): (config, weights file, pipeline, ids [2, 30],
    reference logits)."""
    config = _config()
    path = weights.write(config, 2 ** 31 + 7, str(
        tmp_path_factory.mktemp("kimi") / "weights.npz"))
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.float32,
        model_file=path)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)
    return config, path, pipe, ids, wanted


# float32 program against float32 reference: they differ by the order of
# their sums (1e-7 of the logits' range measured); 1e-5 leaves room for
# another BLAS and would fail a bfloat16 product (2e-3) two hundred times over
TOLERANCE = 1e-5


# the tiny model prefills in spans of 8: within one, at its end, across two
@pytest.mark.parametrize("prompt_len", [3, 8, 13, 24])
def test_spans_then_decode_match_the_reference(prompt_len, tiny):
    _, _, pipe, ids, wanted = tiny
    got = _logits_through_the_cache(pipe, ids, prompt_len)
    wanted = wanted[:, prompt_len - 1:]
    assert np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


def test_the_whole_model_matches_the_reference(tmp_path):
    """All 8 experts and the whole vocabulary: the uncut registry entry."""
    config = _config(n_routed_experts=8, vocab_size=100)
    path = weights.write(config, 11, str(tmp_path / "weights.npz"))
    pipe = decode.build_decode_pipeline(TINY, None, max_len=32,
                                        dtype=jnp.float32, model_file=path)
    ids = np.random.default_rng(4).integers(0, 100, size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)[:, 12:]
    got = _logits_through_the_cache(pipe, ids, 13)
    assert np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


@pytest.mark.parametrize("limit", ["SCORE_BYTES", "PRODUCT_BYTES"])
def test_chunks_of_queries_and_of_rows_change_nothing(limit, tiny,
                                                      monkeypatch):
    """At real sizes a span's scores and its widest three-pass products run
    in chunks; forced here: two queries, and four rows, a chunk."""
    config, path, _, ids, wanted = tiny
    monkeypatch.setattr(decoder, limit, {"SCORE_BYTES": 2 * 4 * 2 * 40 * 4,
                                         "PRODUCT_BYTES": 4 * 64 * 12}[limit])
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.float32,
        model_file=path)
    data, _ = pipe._prefill(jnp.asarray(ids[:, :24], jnp.int32))
    spread = wanted[:, 23].max() - wanted[:, 23].min()
    assert np.abs(np.asarray(data[:, -1]) - wanted[:, 23]).max() \
        <= TOLERANCE * spread


def test_bfloat16_weights_are_computed_on_in_float32(tiny):
    """The seeded values are ones a bfloat16 holds, so the program's
    bfloat16 weights are the reference's float32 ones, and its float32
    activations over them give the reference's logits."""
    config, path, _, ids, wanted = tiny
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.bfloat16,
        model_file=path)
    blocks = pipe.stages[0]["params"]["blocks"]
    assert blocks.runs[1]["experts"]["gate"].dtype == jnp.bfloat16
    assert blocks.runs[1]["router"]["bias"].dtype == jnp.float32
    got = _logits_through_the_cache(pipe, ids, 24)
    wanted = wanted[:, 23:]
    assert np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


def _attention_inputs(dtype=jnp.float32):
    cfg = registry.get_model_config(TINY)
    rng = np.random.default_rng(0)

    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.5, size=shape), dtype)

    p = {"w_uk": mat(4, 8, 16), "w_uv": mat(4, 8, 16)}
    return cfg, p, mat(2, 6, 4, 8), mat(2, 6, 4, 8), mat(2, 6, 16), \
        mat(2, 6, 8)


@pytest.mark.parametrize("split", [0, 2, 6])
def test_absorbed_equals_expanded(split):
    """The same six keys attended expanded, absorbed, and the first `split`
    absorbed beside the rest expanded (a span over a cached window): one
    function."""
    cfg, p, q_nope, q_pe, c_kv, k_pe = _attention_inputs()
    causal = jnp.tril(jnp.ones((6, 6), bool))
    k_nope, v = kimi.expand(p, c_kv)
    wanted = kimi.latent_attention(p, q_nope, q_pe, [],
                                   (k_nope, v, k_pe, causal), cfg)
    latent = [(c_kv[:, :split], k_pe[:, :split], causal[:, :split])] \
        if split else []
    own = (k_nope[:, split:], v[:, split:], k_pe[:, split:],
           causal[:, split:]) if split < 6 else None
    got = kimi.latent_attention(p, q_nope, q_pe, latent, own, cfg)
    np.testing.assert_allclose(got, wanted, atol=1e-5)
    # and against the plain sum over heads
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe)) \
        * kimi.attention_scale(cfg)
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    plain = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(2, 6, -1)
    np.testing.assert_allclose(got, plain, atol=1e-5)


def test_yarn_frequencies_and_scale_are_the_published_numbers():
    cfg = registry.get_model_config("moonshotai/Kimi-K2-Instruct")
    plain = 50000.0 ** (-np.arange(32) / 32.0)
    freqs = kimi.yarn_frequencies(cfg)
    # the correction range is 19.17: 0-19 stay, 20-31 are divided by 32
    np.testing.assert_allclose(freqs[:20], plain[:20], rtol=1e-6)
    np.testing.assert_allclose(freqs[20:], plain[20:] / 32, rtol=1e-6)
    assert abs(kimi.attention_scale(cfg) - 0.13087) < 2e-5
    config = _config(tiny=False)
    np.testing.assert_allclose(reference.frequencies(config), freqs,
                               rtol=1e-7)
    assert abs(reference.softmax_scale(config) - 0.13087) < 2e-5


def test_rotation_keeps_the_checkpoints_pair_layout():
    cfg = registry.get_model_config(TINY)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 4, 8)),
                    jnp.float32)
    pos = jnp.arange(3, 8)
    angles = np.asarray(pos, np.float32)[:, None] \
        * reference.frequencies(_config())[None]
    wanted = jnp.stack([reference.rotate(row, angles) for row in x])
    np.testing.assert_allclose(kimi.rotate(x, pos, cfg), wanted, atol=1e-6)
    # the pair (2i, 2i+1) turns by frequency i
    i, t = 1, 2
    a, b = np.asarray(x[0, t, 0, 2 * i:2 * i + 2])
    c, s = np.cos(angles[t, i]), np.sin(angles[t, i])
    got = np.asarray(kimi.rotate(x, pos, cfg)[0, t, 0])
    np.testing.assert_allclose([got[i], got[4 + i]],
                               [a * c - b * s, b * c + a * s], atol=1e-6)


@pytest.mark.parametrize("case", ["spread", "ties"])
def test_router_is_the_sorted_top_k_of_score_plus_bias(case):
    cfg = registry.get_model_config("moonshotai/Kimi-K2-Instruct")
    rng = np.random.default_rng(5)
    tokens = rng.normal(size=(40, 16)).astype(np.float32)
    w = rng.normal(size=(16, 384)).astype(np.float32)
    bias = rng.normal(0, 0.3, size=384).astype(np.float32)
    if case == "ties":      # equal columns: equal scores, equal biases
        w[:, 1::2], bias[1::2] = w[:, 0::2], bias[0::2]
    experts, gates = expert.topk_route(
        {"w": jnp.asarray(w), "bias": jnp.asarray(bias)},
        jnp.asarray(tokens), cfg)
    scores = 1.0 / (1.0 + np.exp(-(tokens.astype(np.float64) @ w)))
    for t in range(40):
        order = sorted(range(384),
                       key=lambda e: (-np.float32(scores[t, e] + bias[e]),
                                      e))[:8]
        assert sorted(np.asarray(experts[t]).tolist()) == sorted(order)
        kept = scores[t, np.asarray(experts[t])]
        np.testing.assert_allclose(gates[t], kept / kept.sum() * 2.827,
                                   rtol=1e-5)
    np.testing.assert_allclose(gates.sum(-1), 2.827, rtol=1e-5)


def _expert_layer():
    cfg = registry.get_model_config(TINY)
    rng = np.random.default_rng(2)
    d, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_experts

    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, size=shape), jnp.float32)

    params = {"router": {"w": mat(d, e), "bias": mat(e)},
              "experts": {"gate": mat(e, f, d), "up": mat(e, f, d),
                          "down": mat(e, d, f)},
              "shared": {"gate": mat(f, d), "up": mat(f, d),
                         "down": mat(d, f)}}
    return cfg, params, mat(2, 5, d)


def _plain_layer(cfg, params, x):
    """The uncut layer as the reference has it: the shared expert, and each
    chosen expert in turn."""
    tokens = x.reshape(-1, x.shape[-1])
    experts, gates = reference.route(
        tokens, params["router"]["w"].T, params["router"]["bias"],
        cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    out = np.asarray(reference._swiglu(tokens, *(
        params["shared"][name] for name in ("gate", "up", "down")))).copy()
    for t in range(tokens.shape[0]):
        for e, gate in zip(np.asarray(experts[t]), np.asarray(gates[t])):
            out[t] += gate * np.asarray(reference._swiglu(
                tokens[t:t + 1], *(params["experts"][name][e]
                                   for name in ("gate", "up", "down"))))[0]
    return out.reshape(x.shape)


def test_four_shares_of_two_experts_and_the_shared_once_add_up():
    cfg, params, x = _expert_layer()
    whole, stats = expert.topk_ffn_delta(params, x, cfg)
    wanted = _plain_layer(cfg, params, x)
    np.testing.assert_allclose(whole, wanted, atol=1e-5)
    assert stats[0] == 2 * 5 * cfg.num_experts_per_tok
    total, assigned = 0.0, 0.0
    for first in range(0, cfg.n_experts, 2):
        mine = {"router": params["router"], "experts": {
            name: leaf[first:first + 2]
            for name, leaf in params["experts"].items()}}
        if first == 0:      # every chip computes it alike: counted once
            mine["shared"] = params["shared"]
        share = dataclasses.replace(cfg, held_experts=(first, 2))
        delta, counts = expert.topk_ffn_delta(mine, x, share)
        total, assigned = total + delta, assigned + float(counts[0])
    np.testing.assert_allclose(total, wanted, atol=1e-5)
    assert assigned == float(stats[0])


def test_a_share_builds_nothing_of_the_size_assignments_x_hidden():
    """A tile's rows are gathered inside the tile loop, and the tiles'
    results are kept a round at a time: the largest value of hidden width
    the expert layer of a share builds has no more rows than tokens."""
    cfg, params, _ = _expert_layer()
    cfg = dataclasses.replace(cfg, held_experts=(0, 1))
    mine = dict(params, experts={name: leaf[:1] for name, leaf
                                 in params["experts"].items()})
    x = jax.ShapeDtypeStruct((4, 1024, cfg.hidden_size), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda y: expert.topk_ffn_delta(mine, y, cfg))(x)
    tokens, assignments = 4 * 1024, 4 * 1024 * cfg.num_experts_per_tok

    def rows_of(var):
        shape = getattr(var.aval, "shape", ())
        return shape[0] if len(shape) == 2 \
            and shape[1] == cfg.hidden_size else 0

    def walk(eqns):
        for eqn in eqns:
            yield from (rows_of(var) for var in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub.eqns)

    assert max(walk(jaxpr.jaxpr.eqns)) == tokens < assignments


@pytest.mark.parametrize("slack", [4, 1])
def test_skewed_routing_takes_more_rounds_and_drops_nothing(slack,
                                                            monkeypatch):
    """Every token chooses the one held expert, eight times what an even
    router would give it: the tiles' results no longer fit one round."""
    monkeypatch.setattr(expert, "ROUND_SLACK", slack)
    cfg, params, _ = _expert_layer()
    params = dict(params, router={
        "w": params["router"]["w"],
        "bias": jnp.asarray([9.0] + [0.0] * 7, jnp.float32)})
    # 1,200 tokens, all of them the one expert's: a group of as many tiles
    # as the rule's tile for an even router's 300 goes into 1,200; a round
    # keeps what the slack allows an even router and one tile a held expert
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 600, 32)),
                    jnp.float32)
    tile = expert.expert_tile(1200, cfg.num_experts_per_tok, cfg.n_experts)
    tiles = -(-1200 // tile)
    a_round = -(-slack * 1200 * cfg.num_experts_per_tok
                // (cfg.n_experts * tile)) + 1
    assert (a_round < tiles) == (slack == 1) and tiles > 1
    wanted, _ = expert.topk_ffn_delta(params, x, cfg)
    total = 0.0
    for first, count in ((0, 1), (1, 7)):
        mine = {"router": params["router"], "experts": {
            name: leaf[first:first + count]
            for name, leaf in params["experts"].items()}}
        if first == 0:
            mine["shared"] = params["shared"]
        delta, counts = expert.topk_ffn_delta(
            mine, x, dataclasses.replace(cfg, held_experts=(first, count)))
        if first == 0:
            assert counts[0] == 1200 and counts[1] == tiles * tile
        total = total + delta
    np.testing.assert_allclose(total, wanted, atol=1e-4)
    np.testing.assert_allclose(wanted[:, :3], _plain_layer(
        cfg, params, x)[:, :3], atol=1e-4)


# (tokens, top-k, experts) -> the tile: the eight layer calls of the four
# sparse cells (a step and a prefill span each), then the edges
TILES = {
    "lfm2-step": (128, 4, 32, 32), "lfm2-span": (16384, 4, 32, 256),
    "keye-step": (8, 8, 128, 8), "keye-span": (4096, 8, 128, 256),
    "qwen3-next-step": (8, 10, 512, 8),
    "qwen3-next-span": (8192, 10, 512, 200),
    "kimi-step": (32, 8, 384, 8), "kimi-span": (4096, 8, 384, 120),
    "fewer-than-8-tokens": (3, 2, 8, 8), "one-token": (1, 8, 384, 8),
    "mean-under-1": (64, 1, 1024, 8), "every-expert": (40, 8, 8, 40),
    "one-expert": (1200, 1, 1, 256), "tiny-span": (16, 2, 8, 16),
}


@pytest.mark.parametrize("case", list(TILES))
def test_a_tile_holds_a_group_and_follows_the_load(case):
    tokens, per_tok, experts, wanted = TILES[case]
    tile = expert.expert_tile(tokens, per_tok, experts)
    assert tile == wanted
    assert tile % 8 == 0 and 8 <= tile <= expert.EXPERT_TILE
    assert tile <= -(-tokens // 8) * 8
    # the mean group fits, or the tile is the cap
    assert tile >= min(tokens * per_tok / experts, expert.EXPERT_TILE)
    # more tokens, more choices a token or fewer experts: never a smaller
    # tile
    for more in (1, 2, 3, 5, 8, 64):
        grown = tokens * more + more - 1
        assert expert.expert_tile(grown, per_tok, experts) >= tile
        assert expert.expert_tile(
            tokens, min(per_tok + more, experts), experts) >= tile
        assert expert.expert_tile(
            tokens, per_tok, max(experts // (more + 1), per_tok)) >= tile


def _skewed_layer():
    """100 tokens over the tiny layer's eight experts, the bias so skewed
    that expert 0 is given every token, expert 7 most, the others a few:
    groups of three tiles, two and one."""
    cfg, params, _ = _expert_layer()
    params = dict(params, router={
        "w": params["router"]["w"] * 0.25,
        "bias": jnp.asarray([9.0] + [0.0] * 6 + [0.2], jnp.float32)})
    x = jnp.asarray(np.random.default_rng(11).normal(size=(4, 25, 32)),
                    jnp.float32)
    chosen, _ = reference.route(
        x.reshape(-1, 32), params["router"]["w"].T, params["router"]["bias"],
        cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    sizes = np.bincount(np.asarray(chosen).reshape(-1), minlength=8)
    return cfg, params, x, sizes


@pytest.mark.parametrize("how", ["whole", "shares", "layer"])
def test_groups_of_one_two_and_three_tiles_match_the_plain_layer(how):
    cfg, params, x, sizes = _skewed_layer()
    tile = expert.expert_tile(100, cfg.num_experts_per_tok, cfg.n_experts)
    tiles = -(-sizes // tile)
    assert set(tiles.tolist()) >= {1, 2, 3}
    # the last group's last tile runs past the last assignment
    assert sizes[7] % tile and sizes.sum() == 200
    wanted = _plain_layer(cfg, params, x)
    if how == "shares":
        total = 0.0
        for first, count in ((0, 3), (3, 5)):
            mine = {"router": params["router"], "experts": {
                name: leaf[first:first + count]
                for name, leaf in params["experts"].items()}}
            if first == 0:
                mine["shared"] = params["shared"]
            delta, counts = expert.topk_ffn_delta(
                mine, x, cfg, held=(first, count))
            held = slice(first, first + count)
            assert counts.tolist() == [sizes[held].sum(),
                                       tiles[held].sum() * tile,
                                       (sizes[held] > 0).sum(), 0]
            total = total + delta
        np.testing.assert_allclose(total, wanted, atol=1e-5)
        return
    layer = None
    if how == "layer":      # the stacked blocks' leaves, this block the 2nd
        params = dict(params, experts={
            name: jnp.stack([jnp.full_like(leaf, jnp.nan), leaf])
            for name, leaf in params["experts"].items()})
        layer = jnp.int32(1)
    delta, counts = jax.jit(lambda p, y, at: expert.topk_ffn_delta(
        p, y, cfg, layer=at))(params, x, layer)
    np.testing.assert_allclose(delta, wanted, atol=1e-5)
    assert counts.tolist() == [200, tiles.sum() * tile, (sizes > 0).sum(),
                               0]


def _counters():
    return {(name, phase): prom.REGISTRY.counter(
        f"pipeedge_{name}_total", "").value(phase=phase)
        for name in kimi.STATS for phase in ("prefill", "decode")}


def test_counters_of_one_batch_are_what_its_sizes_predict(tiny, tmp_path):
    config, _, pipe, ids, _ = tiny
    before = _counters()
    out = np.asarray(pipe.generate(ids[:, :20], 8))
    gained = {key: value - before[key]
              for key, value in _counters().items()}
    rows, layers, expert_layers, steps, spans = 2, 3, 2, 7, 3
    assert gained["mla_rows_written", "prefill"] == rows * 20 * layers
    assert gained["mla_rows_expanded", "prefill"] == rows * 20 * layers
    assert gained["mla_rows_read", "prefill"] == rows * (8 + 16) * layers
    assert gained["mla_rows_written", "decode"] == rows * steps * layers
    assert gained["mla_rows_expanded", "decode"] == 0
    assert gained["mla_rows_read", "decode"] == rows * layers \
        * sum(range(20, 27))
    assert gained["moe_layer_calls", "prefill"] == spans * expert_layers
    assert gained["moe_layer_calls", "decode"] == steps * expert_layers
    # the held assignments are those the reference routes to experts 0, 1
    record = []
    from benchmark import weights as seeded
    path = seeded.write(config, 2 ** 31 + 7, str(tmp_path / "w.npz"))
    with np.load(path) as tensors:
        reference.forward(config, tensors, out[:, :-1], record=record)
    held = {"prefill": 0, "decode": 0}
    for layer in record:
        mine = layer["experts"] < 2
        held["prefill"] += int(mine[:20].sum())
        held["decode"] += int(mine[20:].sum())
    for phase in ("prefill", "decode"):
        assert gained["moe_assignments", phase] == held[phase] > 0
        assert gained["moe_rows_computed", phase] \
            >= gained["moe_assignments", phase]
        assert gained["moe_experts_touched", phase] \
            <= 2 * gained["moe_layer_calls", phase]


def test_a_cut_keeps_the_dense_layer_and_the_first_expert_layers(tiny,
                                                                 tmp_path):
    config, path, _, _, _ = tiny
    # the whole model's file: a cut reads its own part of it
    uncut = weights.write(_config(n_routed_experts=8, vocab_size=100), 5,
                          str(tmp_path / "whole.npz"))
    whole = registry.module_shard_factory(TINY, uncut, 1, 12,
                                          unroll=False)[1]
    assert isinstance(whole["blocks"], BlockRuns)
    dense, experts = whole["blocks"].runs
    assert "mlp" in dense and "router" not in dense
    assert jax.tree_util.tree_leaves(dense)[0].shape[0] == 1
    assert experts["experts"]["gate"].shape == (2, 8, 16, 32)
    cut = registry.get_model_entry(TINY + "@2,e2+3,v50")
    assert cut.layers == 8 and cut.config.held_experts == (2, 3)
    assert cut.weights_file == "test-tiny-kimi@2,e2+3,v50.npz"
    assert dataclasses.replace(
        cut.config, num_hidden_layers=3, held_experts=(),
        vocab_size=100) == registry.get_model_config(TINY)
    first = registry.module_shard_factory(TINY + "@2,e2+3,v50", uncut, 1, 8,
                                          unroll=False)[1]
    jax.tree_util.tree_map(np.testing.assert_array_equal, dense,
                           first["blocks"].runs[0])
    one = first["blocks"].runs[1]
    np.testing.assert_array_equal(one["experts"]["up"],
                                  experts["experts"]["up"][:1, 2:5])
    np.testing.assert_array_equal(one["router"]["w"],
                                  experts["router"]["w"][:1])
    np.testing.assert_array_equal(first["embeddings"]["wte"],
                                  whole["embeddings"]["wte"][:50])
    np.testing.assert_array_equal(first["final"]["head"]["w"],
                                  whole["final"]["head"]["w"][:50])
    # the dense layer alone is one run, a bare stack
    alone = registry.module_shard_factory(TINY + "@1", None, 1, 4,
                                          unroll=False)[1]
    assert not isinstance(alone["blocks"], BlockRuns)
    # the benchmark's cut reads the file's experts by their published index
    mine = registry.module_shard_factory(config["program_model"], path, 1,
                                         12, unroll=False)[1]
    with np.load(path) as tensors:
        np.testing.assert_array_equal(
            mine["blocks"].runs[1]["experts"]["down"][1, 1],
            tensors["model.layers.2.mlp.experts.1.down_proj.weight"])


@pytest.mark.parametrize("cut", ["4", "e7+2", "e0", "v101", "x", "2,e0+9"])
def test_a_cut_the_model_does_not_have_is_refused(cut):
    with pytest.raises(ValueError, match="no cut"):
        registry.get_model_entry(f"{TINY}@{cut}")
    with pytest.raises(ValueError, match="no cut"):
        registry.get_model_entry("pipeedge/test-tiny-gpt2@e0+1")


@pytest.mark.parametrize("asked", ["mesh", "sp_mesh", "ep_mesh",
                                   "tp_ep_mesh", "cache_bits", "forward",
                                   "kv_pages"])
def test_what_the_family_cannot_do_is_refused_by_name(asked):
    from jax.sharding import Mesh
    entry = registry.get_model_entry(TINY)
    _, params, stage = registry.module_shard_factory(TINY, None, 1, 12,
                                                     unroll=False)
    if asked == "forward":
        with pytest.raises(NotImplementedError, match="runs of"):
            shard_apply(entry.family.FAMILY, entry.config, stage, params,
                        jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(NotImplementedError, match="kimi"):
            kimi.FAMILY.sublayer({}, 0, None, entry.config)
        return
    if asked == "kv_pages":
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import serve
        pipe = decode.DecodePipeline(entry.family.FAMILY, entry.config,
                                     [(1, 12)], [params], max_len=32)
        with pytest.raises(NotImplementedError, match="kimi"):
            serve._Service(pipe, kv_pages=4)
        return
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
        decode.DecodePipeline(entry.family.FAMILY, entry.config, [(1, 12)],
                              [params], max_len=32, **option)


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_costs_against_hand_counts(size):
    config = _config(tiny=size == "tiny")
    if size == "tiny":
        # q_a 32x24, q_b 24x64, kv_a 32x24, kv_b 64x16, o 32x32
        assert costs_kimi.attention_params(config) \
            == 768 + 1536 + 768 + 1024 + 1024
        assert costs_kimi.dense_ffn_params(config) == 3 * 32 * 64
        assert costs_kimi.expert_params(config) == 3 * 32 * 16
        # the shared expert and a router of 8
        assert costs_kimi.expert_layer_fixed_params(config) == 1536 + 256
        assert costs_kimi.held_parameters(config) == 3 * 5120 + 6144 \
            + 2 * (1792 + 2 * 1536) + 2 * 32 * 50
        assert costs_kimi.cache_bytes_a_token(config) == 3 * 24 * 4
        assert costs_kimi.expected_held_a_token(config) == 0.5
        products = 2 * (3 * 5120 + 6144 + 2 * (1792 + 0.5 * 1536))
        assert costs_kimi.token_product_flops(config) == products
        # a pair: 4 heads x (16 + 8) x 2; a latent row: 4 x (24 + 16) x 2
        assert costs_kimi.expanded_pair_flops(config) == 192
        assert costs_kimi.absorbed_row_flops(config) == 320
        assert costs_kimi.prefill_flops(config, 2, 20) == 2 * (
            20 * products + 3 * 192 * 210 + 2 * 32 * 50)
        assert costs_kimi.decode_step_flops(config, 2, 21) == 2 * (
            products + 3 * 320 * 21 + 2 * 32 * 50)
        assert costs_kimi.decode_step_bytes(config, 2, 21, 1.5) == 2 * (
            3 * 5120 + 6144 + 2 * (1792 + 1.5 * 1536) + 32 * 50) \
            + 2 * 21 * 288
        return
    # ISSUE 31's arithmetic: MLA 101.1 M a layer (11.0 + 18.9 + 4.1 + 8.4 +
    # 58.7), the dense FFN 396.4 M, an expert 44.0 M, the router 2.8 M;
    # 3.50 G parameters; 11,520 B of float32 cache a token
    assert costs_kimi.attention_params(config) == 11010048 + 18874368 \
        + 4128768 + 8388608 + 58720256 == 101122048
    assert costs_kimi.dense_ffn_params(config) == 396361728
    assert costs_kimi.expert_params(config) == 44040192
    assert costs_kimi.expert_layer_fixed_params(config) \
        == 44040192 + 7168 * 384
    assert costs_kimi.held_parameters(config) == 5 * 101122048 + 396361728 \
        + 4 * (46792704 + 12 * 44040192) + 2 * 7168 * 20480 == 3496673280
    assert costs_kimi.cache_bytes_a_token(config) == 11520
    assert costs_kimi.expanded_pair_flops(config) == 40960
    assert costs_kimi.absorbed_row_flops(config) == 139264
    assert 2.26e9 < costs_kimi.token_product_flops(config) < 2.28e9
    assert 0.50e15 < costs_kimi.prefill_flops(config, 64, 3072) < 0.52e15
    # 5.8 GB of weights with 8.9 experts touched, 2.6 GB of latent
    assert 8.2e9 < costs_kimi.decode_step_bytes(config, 64, 3584, 8.9) \
        < 8.3e9
    assert 3.2e11 < costs_kimi.decode_step_flops(config, 64, 3584) < 3.3e11


@pytest.mark.parametrize("model, equations, scans", [
    ("pipeedge/test-tiny-gpt2", 34, 1), ("pipeedge/test-tiny-keye", 38, 1),
    (TINY, None, 2)])
def test_a_run_of_like_blocks_is_one_scan(model, equations, scans):
    """gpt2's and keye's decode step trace to what they traced to before a
    stage could hold runs of blocks (counted on the parent commit of PR
    31): one scan, and not an equation more. Kimi's is two."""
    entry = registry.get_model_entry(model)
    cfg, family = entry.config, entry.family.FAMILY
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    params = jax.eval_shape(lambda: entry.family.init_params(cfg, stage))
    leaves = family.cache_leaves(cfg) if family.cache_leaves else None
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, 2, 32, leaves=leaves))
    run = decode._make_stage_run(family, cfg, stage)
    jaxpr = jax.make_jaxpr(
        lambda p, d, c, pos: run(p, d, c, pos, prefill=False, read_len=32))(
            params, jax.ShapeDtypeStruct((2, 1), jnp.int32), cache,
            jax.ShapeDtypeStruct((), jnp.int32))
    names = collections.Counter(eqn.primitive.name
                                for eqn in jaxpr.jaxpr.eqns)
    assert names["scan"] == scans
    if equations is not None:
        assert len(jaxpr.jaxpr.eqns) == equations
