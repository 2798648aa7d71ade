"""The lfm2 family (models/lfm2.py: gated short convolutions whose state is
two positions a request beside attention layers' keys and values in one
stage's cache, a cache leaf that runs of two kinds own, a dense FFN in the
leading blocks and a sigmoid-routed expert layer after) against the
benchmark's plain reference, on the CPU at `pipeedge/test-tiny-lfm2`, with
seeded weights in the published key scheme."""
import collections
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_lfm2 as costs, weights
from benchmark.reference import lfm2_moe as reference
from pipeedge_tpu.models import ShardConfig, lfm2, registry, stage_cache
from pipeedge_tpu.models.layers import causal_conv
from pipeedge_tpu.models.shard import (BlockRuns, CacheLeaf, kind_runs,
                                       shard_apply)
from pipeedge_tpu.parallel import decode, expert
from pipeedge_tpu.telemetry import metrics as prom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "pipeedge/test-tiny-lfm2"
CELL = "LiquidAI/LFM2-8B-A1B@12"
LENGTH = 30


def _config(tiny=True, **over):
    name = "lfm2-8b-a1b.json"
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
    """The benchmark's tiny cut, eight blocks in one stage: (config, weights
    file, pipeline, ids [2, 30], reference logits)."""
    config = _config()
    path = weights.write(config, 2 ** 31 + 7, str(
        tmp_path_factory.mktemp("lfm2") / "weights.npz"))
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.float32,
        model_file=path)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)
    return config, path, pipe, ids, wanted


# float32 program against float32 reference: they differ by the order of
# their sums (a span's convolution against the whole row's, the experts'
# tiles, whose rows follow the call's load (`expert.expert_tile`), against an
# expert at a time; 1.1e-7 of the logits' range measured);
# 1e-5 leaves room for another BLAS and would fail a bfloat16 product or a
# bfloat16 tail (2e-3) two hundred times over
TOLERANCE = 1e-5


# the tiny model prefills in spans of 8 and its convolution is 3 wide: a
# prompt shorter than the kernel (1, 2), within a span (3), a span (8),
# across a span boundary (13, 21), two and three spans (16, 24)
@pytest.mark.parametrize("prompt_len", [1, 2, 3, 8, 13, 16, 21, 24])
def test_spans_then_decode_match_the_reference(prompt_len, tiny):
    _, _, pipe, ids, wanted = tiny
    got = _logits_through_the_cache(pipe, ids, prompt_len)
    wanted = wanted[:, prompt_len - 1:]
    assert np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


@pytest.fixture(scope="module")
def cut(tiny):
    """`<name>@5` over the same file (a loader reads its own layers' keys):
    the dense pair, an attention block and two routed convolution blocks."""
    _, path, _, _, _ = tiny
    return decode.build_decode_pipeline(TINY + "@5", None, max_len=32,
                                        dtype=jnp.float32, model_file=path)


def test_a_cut_in_depth_matches_the_reference(tiny, cut):
    """`<name>@<depth>` keeps the list of mixers and reads its first
    entries: the convolution's leaf is indexed across the dense pair's run
    and the routed run after it."""
    _, path, _, ids, _ = tiny
    assert cut.stages[0]["runs"] == (("conv_dense", 2), ("attn_experts", 1),
                                     ("conv_experts", 2))
    with np.load(path) as tensors:
        wanted = reference.forward(_config(num_hidden_layers=5), tensors,
                                   ids)[:, 12:]
    got = _logits_through_the_cache(cut, ids, 13)
    assert np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


def test_a_whole_prompt_prefill_is_the_spans(tiny):
    """The served path's prefill program (the whole prompt in one call, the
    tail from zeros and not from the cache) leaves what the spans leave."""
    _, _, pipe, ids, wanted = tiny
    stage = pipe.stages[0]
    data, cache = stage["prefill"](stage["params"],
                                   jnp.asarray(ids[:, :21], jnp.int32),
                                   pipe._fresh_caches(2)[0])
    _, spans = pipe._prefill(jnp.asarray(ids[:, :21], jnp.int32))
    spread = wanted[:, 20].max() - wanted[:, 20].min()
    assert np.abs(np.asarray(data[:, -1]) - wanted[:, 20]).max() \
        <= TOLERANCE * spread
    for name in ("k", "v", "conv_tail"):
        np.testing.assert_allclose(cache[name], spans[0][name], atol=1e-5)


def test_bfloat16_weights_are_computed_on_in_float32(tiny):
    """The seeded values are ones a bfloat16 holds, so the program's
    bfloat16 weights are the reference's float32 ones, and its float32
    activations, cache and tail over them give the reference's logits."""
    config, path, _, ids, wanted = tiny
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.bfloat16,
        model_file=path)
    blocks = pipe.stages[0]["params"]["blocks"]
    assert blocks.runs[0]["conv_in"].dtype == jnp.bfloat16
    assert blocks.runs[1]["experts"]["gate"].dtype == jnp.bfloat16
    assert blocks.runs[1]["router"]["bias"].dtype == jnp.float32
    cache = pipe._fresh_caches(2)[0]
    assert {cache[name].dtype for name in ("k", "v", "conv_tail")} \
        == {jnp.dtype(jnp.float32)}
    got = _logits_through_the_cache(pipe, ids[:, :20], 16)
    wanted = wanted[:, 15:20]
    assert np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


def test_the_attentions_products_of_activations_are_float32_in_full(tiny):
    """On the chip a float32 product without a precision is one bfloat16
    pass: every product of two activations in the step program says
    HIGHEST, and no other family's says anything (they trace as before)."""
    _, _, pipe, _, _ = tiny

    def precisions(model, pipe):
        entry = registry.get_model_entry(model)
        stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
        run = decode._make_stage_run(entry.family.FAMILY, entry.config,
                                     stage)
        text = str(jax.make_jaxpr(lambda p, d, c, pos: run(
            p, d, c, pos, prefill=False, read_len=32))(
                pipe.stages[0]["params"],
                jax.ShapeDtypeStruct((2, 1), jnp.int32),
                pipe._fresh_caches(2)[0],
                jax.ShapeDtypeStruct((), jnp.int32)))
        return text.count("Precision.HIGHEST")

    gpt2 = decode.build_decode_pipeline("pipeedge/test-tiny-gpt2", None,
                                        max_len=32)
    # (conftest.py's "highest" for CPU parity would say it for all of them)
    with jax.default_matmul_precision("default"):
        highest = precisions(TINY, pipe)
        assert precisions("pipeedge/test-tiny-gpt2", gpt2) == 0
    assert highest >= 2 * 2 * 2     # scores and context, window and own
    #                                 rows, in either attention run


# -- the gated short convolution -----------------------------------------------

def _conv_block(seed=0):
    cfg = registry.get_model_config(TINY)
    rng = np.random.default_rng(seed)
    d = cfg.hidden_size

    def mat(*shape):    # weights large enough that every term matters
        return jnp.asarray(rng.normal(0, 0.3, size=shape), jnp.float32)

    block = {"conv_in": mat(3 * d, d), "conv": mat(cfg.conv_kernel, d),
             "conv_out": mat(d, d)}
    return cfg, block, mat(2, 14, d)


def test_the_span_form_is_the_one_token_form_position_by_position():
    cfg, block, x = _conv_block()
    tail = jnp.zeros((2, cfg.conv_kernel - 1, cfg.hidden_size))
    whole, whole_tail = lfm2.short_conv(block, x, tail, cfg)
    for at in range(x.shape[1]):
        one, tail = lfm2.short_conv(block, x[:, at:at + 1], tail, cfg)
        np.testing.assert_allclose(one[:, 0], whole[:, at], atol=1e-5)
    np.testing.assert_allclose(tail, whole_tail, atol=1e-6)
    # and the plain sum over taps, written out: no activation, no bias
    gates = np.asarray(x) @ np.asarray(block["conv_in"]).T
    before, after, u = np.split(gates, 3, axis=-1)
    m = np.concatenate([np.zeros((2, 2, cfg.hidden_size)), before * u], 1)
    kernel = np.asarray(block["conv"])
    c = sum(kernel[j] * m[:, j:j + 14] for j in range(3))
    np.testing.assert_allclose(
        whole, (after * c) @ np.asarray(block["conv_out"]).T, atol=1e-4)


@pytest.mark.parametrize("cut", [1, 2, 5, 8, 13])
def test_the_tail_is_handed_over_between_two_spans(cut):
    """A span cut at any position, shorter than the convolution too: the
    second part takes the last two inputs of the first."""
    cfg, block, x = _conv_block(1)
    tail = jnp.zeros((2, cfg.conv_kernel - 1, cfg.hidden_size))
    whole, whole_tail = lfm2.short_conv(block, x, tail, cfg)
    first, tail = lfm2.short_conv(block, x[:, :cut], tail, cfg)
    second, tail = lfm2.short_conv(block, x[:, cut:], tail, cfg)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=1e-5)
    np.testing.assert_allclose(tail, whole_tail, atol=1e-6)
    assert np.abs(np.asarray(whole_tail)).min() > 0     # two real inputs
    # a lost hand-over shows: from zeros the second part differs
    lost, _ = lfm2.short_conv(block, x[:, cut:], jnp.zeros_like(tail), cfg)
    assert np.abs(np.asarray(lost[:, 0] - whole[:, cut])).max() > 1e-2


@pytest.mark.parametrize("width, span", [(3, 1), (3, 7), (4, 2), (4, 9)])
def test_the_shared_convolution_is_causal_and_carries_its_tail(width, span):
    """`layers.causal_conv`, which this family's mixer and qwen3_next's
    both call: position t sees t - K + 1 .. t, the tail is the last K - 1
    inputs whatever the span."""
    rng = np.random.default_rng(width * span)
    kernel = rng.normal(size=(width, 5)).astype(np.float32)
    before = rng.normal(size=(2, width - 1, 5)).astype(np.float32)
    x = rng.normal(size=(2, span, 5)).astype(np.float32)
    mixed, tail = causal_conv(jnp.asarray(kernel), jnp.asarray(x),
                              jnp.asarray(before))
    row = np.concatenate([before, x], 1)
    for t in range(span):
        wanted = sum(kernel[j] * row[:, t + j] for j in range(width))
        np.testing.assert_allclose(mixed[:, t], wanted, atol=1e-5)
    np.testing.assert_array_equal(tail, row[:, -(width - 1):])


# what the four families' tiny step (span 1) and span (8) programs traced to
# before `causal_conv`, `CacheLeaf.kind` as a tuple, `attend(precision=)`
# and `gate_sum_eps` (the parent commit): equations at the top level and in
# all. Since PR 41 nine more a traced expert layer: its fourth count and the
# way back's select (on the CPU these programs keep the tile loop). Since
# PR 45 keye's masked softmax is `decoder.softmax_over` (the weights divided
# by their sum once, after the values, not a part at a time: eight fewer).
# Since PR 46 qwen3_next's gated layer attends through `decoder.attend_masked`
# too: a KV group's queries are sliced once, not once a key part (eight fewer)
TRACED = {("pipeedge/test-tiny-gpt2", 1): (34, 230),
          ("pipeedge/test-tiny-gpt2", 8): (32, 228),
          ("pipeedge/test-tiny-keye", 1): (38, 747),
          ("pipeedge/test-tiny-keye", 8): (36, 745),
          ("pipeedge/test-tiny-kimi", 1): (37, 755),
          ("pipeedge/test-tiny-kimi", 8): (37, 757),
          ("pipeedge/test-tiny-qwen3-next", 1): (44, 2202),
          ("pipeedge/test-tiny-qwen3-next", 8): (44, 2412)}


def _equations(jaxpr, names):
    for eqn in jaxpr.eqns:
        names[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _equations(inner, names)
    return names


@pytest.mark.parametrize("model, span", sorted(TRACED))
def test_the_other_families_trace_to_what_they_traced_to(model, span):
    pipe = decode.build_decode_pipeline(model, None, max_len=32)
    entry = registry.get_model_entry(model)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    run = decode._make_stage_run(entry.family.FAMILY, entry.config, stage)
    jaxpr = jax.make_jaxpr(
        lambda p, d, c, pos: run(p, d, c, pos, prefill=False, read_len=32))(
            pipe.stages[0]["params"],
            jax.ShapeDtypeStruct((2, span), jnp.int32),
            pipe._fresh_caches(2)[0], jax.ShapeDtypeStruct((), jnp.int32))
    names = _equations(jaxpr.jaxpr, collections.Counter())
    assert (len(jaxpr.jaxpr.eqns), sum(names.values())) \
        == TRACED[model, span]


# -- the loader ----------------------------------------------------------------

def test_the_loader_reads_the_published_keys_into_init_params_shapes(tiny):
    config, path, _, _, _ = tiny
    entry = registry.get_model_entry(TINY)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    with np.load(path) as tensors:
        keys = set(tensors.files)
        loaded = lfm2.load_params(entry.config, stage, tensors)
        # the head is the embedding: one table in the file, read twice
        np.testing.assert_array_equal(
            loaded["final"]["head"]["w"],
            np.asarray(tensors["model.embed_tokens.weight"], np.float32))
    drawn = lfm2.init_params(entry.config, stage)
    shapes = jax.tree_util.tree_map(lambda leaf: (leaf.shape, leaf.dtype),
                                    (loaded, drawn))
    assert shapes[0] == shapes[1]
    assert isinstance(loaded["blocks"], BlockRuns)
    np.testing.assert_array_equal(drawn["embeddings"]["wte"],
                                  drawn["final"]["head"]["w"])
    assert "lm_head.weight" not in keys
    for key in ("model.embedding_norm.weight",
                "model.layers.0.conv.in_proj.weight",
                "model.layers.0.conv.conv.weight",
                "model.layers.0.feed_forward.w1.weight",
                "model.layers.2.self_attn.q_layernorm.weight",
                "model.layers.2.self_attn.out_proj.weight",
                "model.layers.2.feed_forward.expert_bias",
                "model.layers.7.feed_forward.experts.7.w3.weight"):
        assert key in keys
    assert len(keys) == 2 + 8 * 2 + 6 * 3 + 2 * 6 + 2 * 3 + 6 * (2 + 8 * 3)
    # the router's rows come in antithetic pairs and the bias is small
    with np.load(path) as tensors:
        gate = np.asarray(tensors["model.layers.3.feed_forward.gate.weight"])
        bias = np.asarray(tensors["model.layers.3.feed_forward.expert_bias"])
    np.testing.assert_array_equal(gate[1::2], -gate[0::2])
    assert 0 < np.abs(bias).max() < 0.02 * 3 ** 0.5 / 16 + 1e-6


def test_a_tensor_of_another_shape_is_refused_by_its_key(tiny):
    _, path, _, _, _ = tiny
    entry = registry.get_model_entry(TINY)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    with np.load(path) as tensors:
        wrong = dict(tensors)
    wrong["model.layers.1.conv.conv.weight"] = np.zeros((32, 1, 4))
    with pytest.raises(ValueError, match="layers.1.conv.conv.weight"):
        lfm2.load_params(entry.config, stage, wrong)


# -- the router and the expert layer -------------------------------------------

def _route(case, cfg):
    rng = np.random.default_rng(5)
    tokens = rng.normal(size=(40, 16)).astype(np.float32)
    w = rng.normal(size=(16, 32)).astype(np.float32)
    bias = np.zeros(32, np.float32)
    if case == "bias":          # expert 31 is chosen by its bias alone
        bias[31] = 10.0
    elif case == "ties":        # equal columns: equal scores
        w[:, 1::2] = w[:, 0::2]
    elif case == "small":       # scores of 1e-7: the sum's constant shows
        w = w * 0.01
        tokens = tokens * 0.0
        w[0] = 0.0
        tokens[:, 0] = 1.0
        w[0] = -16.0 + 0.01 * rng.normal(size=32)
    experts, gates = expert.topk_route(
        {"w": jnp.asarray(w), "bias": jnp.asarray(bias)},
        jnp.asarray(tokens), cfg)
    scores = 1.0 / (1.0 + np.exp(-(tokens.astype(np.float64) @ w)))
    return tokens, w, bias, np.asarray(experts), np.asarray(gates), scores


@pytest.mark.parametrize("case", ["spread", "bias", "ties", "small"])
def test_the_router_is_the_biased_top_k_of_the_sigmoids(case):
    cfg = registry.get_model_config(CELL)       # 32 outputs, 4 a token
    assert (cfg.router, cfg.gate_sum_eps, cfg.routed_scaling_factor,
            cfg.n_shared_experts) == ("sigmoid", 1e-6, 1.0, 0)
    tokens, w, bias, experts, gates, scores = _route(case, cfg)
    for t in range(40):
        # the largest of score + bias, ties to the lower expert
        order = sorted(range(32), key=lambda e: (
            -np.float32(np.float32(scores[t, e]) + bias[e]), e))[:4]
        if case != "small":     # (scores 1e-7 apart are float32 ties)
            assert sorted(experts[t].tolist()) == sorted(order)
        # the gate is the score alone, over the kept scores' sum + 1e-6
        kept = scores[t, experts[t]]
        np.testing.assert_allclose(gates[t], kept / (kept.sum() + 1e-6),
                                   rtol=2e-5)
    if case == "bias":
        assert (experts == 31).any(axis=-1).all()
        assert gates.sum(-1).max() <= 1.0       # 10.0 is in no gate
    if case == "ties":
        for t in range(40):     # of two equal experts the lower comes first
            for e in experts[t]:
                if e % 2:
                    assert e - 1 in experts[t]
    if case == "small":         # sum of four scores about 4.5e-7
        assert 0.2 < gates.sum(-1).min() and gates.sum(-1).max() < 0.4
        kimi = registry.get_model_config("pipeedge/test-tiny-kimi")
        same = dataclasses.replace(cfg, gate_sum_eps=kimi.gate_sum_eps)
        assert kimi.gate_sum_eps == 1e-20
        _, unguarded = expert.topk_route(
            {"w": jnp.asarray(w), "bias": jnp.asarray(bias)},
            jnp.asarray(tokens), same)
        np.testing.assert_allclose(np.asarray(unguarded).sum(-1), 1.0,
                                   rtol=1e-5)
    # the reference's router makes the same choice
    chosen, weight = reference.route(
        jnp.asarray(tokens), jnp.asarray(w.T), jnp.asarray(bias), 4, 1.0)
    if case != "small":
        np.testing.assert_array_equal(chosen, experts)
        np.testing.assert_allclose(weight, gates, rtol=2e-5)


def _expert_layer(rows):
    """(cfg, params, x [rows, 5, D]) of the tiny layer in float32; the
    router in antithetic pairs as the benchmark's scheme draws it."""
    cfg = registry.get_model_config(TINY)
    rng = np.random.default_rng(2)
    d, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_experts

    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, size=shape), jnp.float32)

    half = mat(d, e // 2)
    params = {"router": {"w": jnp.stack([half, -half], 2).reshape(d, e),
                         "bias": mat(e) * 0.1},
              "experts": {"gate": mat(e, f, d), "up": mat(e, f, d),
                          "down": mat(e, d, f)}}
    return cfg, params, mat(rows, 5, d)


def _plain_experts(params, x):
    """Every token through each of its chosen experts in turn, as the
    reference has the layer -> (delta like x, the experts chosen)."""
    d = x.shape[-1]
    tokens = x.reshape(-1, d)
    experts, gates = reference.route(tokens, params["router"]["w"].T,
                                     params["router"]["bias"], 2, 1.0)
    wanted = np.zeros(tokens.shape, np.float32)
    for t in range(tokens.shape[0]):
        for one, gate in zip(np.asarray(experts[t]), np.asarray(gates[t])):
            # the reference's SwiGLU takes (w1, w2, w3) = (gate, down, up)
            wanted[t] += gate * np.asarray(reference._swiglu(
                tokens[t:t + 1], params["experts"]["gate"][one],
                params["experts"]["down"][one],
                params["experts"]["up"][one]))[0]
    return wanted.reshape(x.shape), np.asarray(experts)


def test_the_expert_layer_is_every_chosen_expert_and_no_shared_one():
    cfg, params, x = _expert_layer(2)
    delta, stats = expert.topk_ffn_delta(params, x, cfg)
    wanted, _ = _plain_experts(params, x)
    np.testing.assert_allclose(delta, wanted, atol=1e-5)
    assert stats[0] == 10 * 2


@pytest.mark.parametrize("activations", ["float32", "bfloat16"])
def test_at_load_an_expert_takes_one_tile_that_follows_its_group(activations):
    """The cell's step in small: 65 tokens, top-2 of 8, so 16 an expert
    where the call's tokens rounded to 8 are 72. The tile holds a group
    with room and no more, whatever the activations are stored in."""
    cfg, params, x = _expert_layer(13)
    tile = expert.expert_tile(65, cfg.num_experts_per_tok, cfg.n_experts)
    assert tile == 32
    wanted, chosen = _plain_experts(params, x)
    sizes = np.bincount(chosen.reshape(-1), minlength=cfg.n_experts)
    assert 0 < sizes.min() and sizes.max() <= tile
    delta, stats = expert.topk_ffn_delta(params, x.astype(activations), cfg)
    assert delta.dtype == activations
    if activations == "float32":
        np.testing.assert_allclose(delta, wanted, atol=1e-5)
        assert stats.tolist() == [130, cfg.n_experts * tile, cfg.n_experts,
                                  0]
    else:       # a bfloat16 router may choose otherwise; the tile is the same
        assert stats[0] == 130 and stats[1] % tile == 0
        assert stats[1] <= (cfg.n_experts + 1) * tile


# -- a cache leaf that runs of two kinds own -------------------------------------

def _fresh_cache(model, rows, max_len):
    entry = registry.get_model_entry(model)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    runs = kind_runs(entry.family.FAMILY, entry.config, stage)
    return runs, jax.eval_shape(lambda: stage_cache.init_cache(
        entry.config, entry.config.num_hidden_layers, rows, max_len,
        leaves=lfm2.cache_leaves(entry.config), runs=runs))


@pytest.mark.parametrize("size", ["tiny", "tiny@5", "published"])
def test_a_fresh_cache_holds_each_leaf_for_its_owners_layers(size):
    if size == "tiny":
        runs, cache = _fresh_cache(TINY, 2, 32)
        assert runs == (("conv_dense", 2), ("attn_experts", 1),
                        ("conv_experts", 3), ("attn_experts", 1),
                        ("conv_experts", 1))
        shapes = {"k": (2, 2, 32, 16), "v": (2, 2, 32, 16),
                  "conv_tail": (6, 2, 2, 32), "stats": (8, 8, 2)}
    elif size == "tiny@5":
        runs, cache = _fresh_cache(TINY + "@5", 2, 32)
        shapes = {"k": (1, 2, 32, 16), "v": (1, 2, 32, 16),
                  "conv_tail": (4, 2, 2, 32), "stats": (5, 8, 2)}
    else:       # the cell: 12 blocks, seven runs, 128 rows, 1,024 positions
        runs, cache = _fresh_cache(CELL, 128, 1024)
        assert runs == (("conv_dense", 2), ("attn_experts", 1),
                        ("conv_experts", 3), ("attn_experts", 1),
                        ("conv_experts", 3), ("attn_experts", 1),
                        ("conv_experts", 1))
        shapes = {"k": (3, 128, 1024, 512), "v": (3, 128, 1024, 512),
                  "conv_tail": (9, 128, 2, 2048), "stats": (12, 8, 2)}
    assert {name: leaf.shape for name, leaf in cache.items()} == shapes
    if size == "published":
        held = sum(leaf.size * leaf.dtype.itemsize
                   for name, leaf in cache.items() if name != "stats")
        # keys and values in THREE layers, two positions a request in NINE;
        # twelve layers of keys and values would be 6.4 GB
        assert held == 128 * 1024 * 12288 + 128 * 147456 == 1629487104
        config = _config(tiny=False)
        assert costs.kv_bytes_a_token(config) == 12288
        assert costs.tail_bytes_a_row(config) == 147456


@pytest.mark.parametrize("depth, scans, conv, attn",
                         [(8, 5, 6, 2), (5, 3, 4, 1)])
def test_each_run_indexes_its_leaves_past_the_runs_that_share_them(
        depth, scans, conv, attn, tiny, cut):
    """The convolution's leaf belongs to blocks of two kinds: the routed
    runs follow the dense pair's in it (layers 0-1, then 2-4, then 5), the
    attention's at 0 and 1 of theirs, one scan a run."""
    _, _, whole, ids, _ = tiny
    model, pipe = (TINY, whole) if depth == 8 else (TINY + "@5", cut)
    blocks = pipe.stages[0]["params"]["blocks"]
    assert isinstance(blocks, BlockRuns) and len(blocks.runs) == scans
    _, caches = pipe._prefill(jnp.asarray(ids[:, :13], jnp.int32))
    cache = caches[0]
    tails = np.asarray(cache["conv_tail"]).reshape(conv, -1)
    assert np.abs(tails).min(axis=1).min() > 0      # every layer written
    assert len({row.tobytes() for row in tails}) == conv    # by its own block
    for layer in range(attn):
        rows = np.asarray(cache["k"][layer])
        assert np.abs(rows[:, :13]).min() > 0 and not rows[:, 13:].any()
    # the cut's layers are the whole model's first: the same blocks wrote
    # the same layers of the leaf (a run indexed from 0 again would not)
    if depth < 8:
        _, full = whole._prefill(jnp.asarray(ids[:, :13], jnp.int32))
        np.testing.assert_allclose(cache["conv_tail"],
                                   full[0]["conv_tail"][:conv], atol=1e-6)
        np.testing.assert_allclose(cache["k"], full[0]["k"][:attn],
                                   atol=1e-6)
    entry = registry.get_model_entry(model)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    run = decode._make_stage_run(entry.family.FAMILY, entry.config, stage)
    jaxpr = jax.make_jaxpr(
        lambda p, d, c, pos: run(p, d, c, pos, prefill=False, read_len=32))(
            pipe.stages[0]["params"], jax.ShapeDtypeStruct((2, 1), jnp.int32),
            cache, jax.ShapeDtypeStruct((), jnp.int32))
    names = collections.Counter(eqn.primitive.name
                                for eqn in jaxpr.jaxpr.eqns)
    assert names["scan"] == scans


def test_a_leaf_says_one_kind_or_several_and_a_kinds_leaves_agree():
    row = jax.ShapeDtypeStruct((4,), jnp.float32)
    leaves = {"k": CacheLeaf((4,), jnp.float32, ("a_x", "a_y")),
              "state": CacheLeaf((4,), jnp.float32, "b", whole=True),
              "stats": row}
    owner = stage_cache.leaf_owners(leaves)
    assert owner == {"k": ("a_x", "a_y"), "state": ("b",)}
    assert stage_cache.shares_layers(owner, "a_y") == ("a_x", "a_y")
    assert stage_cache.shares_layers(owner, "b") == ("b",)
    assert stage_cache.shares_layers(owner, "c") == ()
    cfg = registry.get_model_config(TINY)
    runs = (("a_x", 2), ("b", 1), ("a_y", 3), ("b", 2))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, 8, 1, 16, leaves=leaves, runs=runs))
    assert cache["k"].shape == (5, 1, 16, 4)
    assert cache["state"].shape == (3, 1, 4)
    # blocks of one kind cannot count two leaves' layers differently
    leaves["v"] = CacheLeaf((4,), jnp.float32, "a_x")
    with pytest.raises(ValueError, match="one layer index"):
        stage_cache.shares_layers(stage_cache.leaf_owners(leaves), "a_x")


def test_leaves_of_kinds_need_the_stages_runs():
    cfg = registry.get_model_config(TINY)
    with pytest.raises(ValueError, match="runs of kinds"):
        stage_cache.init_cache(cfg, 8, 1, 16, leaves=lfm2.cache_leaves(cfg))


@pytest.mark.parametrize("model, widths", [(TINY, 2), (CELL, 2)])
def test_a_job_asks_for_two_widths_an_octave_where_few_blocks_attend(
        model, widths):
    """2 of 8 and 3 of 12 blocks keep a row a position: fewer than half
    (`decode.job_per_octave`, which reads a leaf's kinds as a tuple too)."""
    entry = registry.get_model_entry(model)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    stages = [{"runs": kind_runs(entry.family.FAMILY, entry.config, stage)}]
    assert decode.job_per_octave(lfm2.cache_leaves(entry.config),
                                 stages) == widths


def _counters():
    return {(name, phase): prom.REGISTRY.counter(
        f"pipeedge_{name}_total", "").value(phase=phase)
        for name in lfm2.STATS for phase in ("prefill", "decode")}


def test_counters_of_one_batch_are_what_its_sizes_predict(tiny):
    _, _, pipe, ids, _ = tiny
    before = _counters()
    pipe.generate(ids[:, :21], 8)
    gained = {key: value - before[key] for key, value in _counters().items()}
    # 2 rows x 21 positions x 6 convolution layers, in spans of 8, 8 and 5
    assert gained["shortconv_positions_spanned", "prefill"] == 2 * 21 * 6
    assert gained["shortconv_positions_stepped", "prefill"] == 0
    # the first span's tail is zeros; the two after it carry one
    assert gained["shortconv_tail_carries", "prefill"] == 2 * 6
    assert gained["shortconv_positions_spanned", "decode"] == 0
    assert gained["shortconv_positions_stepped", "decode"] == 2 * 7 * 6
    assert gained["shortconv_tail_carries", "decode"] == 7 * 6
    # six routed layers of eight; every expert held: 2 a token a layer
    assert gained["moe_layer_calls", "prefill"] == 3 * 6
    assert gained["moe_layer_calls", "decode"] == 7 * 6
    assert gained["moe_assignments", "prefill"] == 2 * 21 * 6 * 2
    assert gained["moe_assignments", "decode"] == 2 * 7 * 6 * 2
    assert 0 < gained["moe_experts_touched", "decode"] <= 7 * 6 * 4


# -- what it runs, and what it refuses by name ----------------------------------

def test_a_prefix_is_a_tail_and_rows_broadcast_over_the_batch(tiny):
    _, _, pipe, ids, _ = tiny
    whole = np.asarray(pipe.generate(ids[:, :21], 6))
    handle = pipe.precompute_prefix(ids[0, :13])
    suffix = np.repeat(ids[:1, 13:21], 2, axis=0)
    got = np.asarray(pipe.generate(suffix, 6, prefix=handle))
    for row in got:
        np.testing.assert_array_equal(row[8:], whole[0, 21:])


def test_a_handle_from_a_pipeline_of_other_leaves_is_refused(tiny):
    _, _, pipe, ids, _ = tiny
    other = decode.build_decode_pipeline("pipeedge/test-tiny-qwen3-next",
                                         None, max_len=32)
    handle = other.precompute_prefix(np.arange(5))
    with pytest.raises(ValueError, match="incompatible pipeline") as caught:
        pipe.generate(ids[:, :4], 2, prefix=handle)
    assert "conv_tail" in str(caught.value) \
        and "gdn_state" in str(caught.value)
    assert pipe._prefix_sig() == decode.build_decode_pipeline(
        TINY, None, max_len=32)._prefix_sig()


@pytest.mark.parametrize("step_join", [False, True])
def test_the_dense_served_path_runs_it(step_join, tiny):
    """`tools/serve.py` without pages: the wave batcher over per-request
    caches, chunked prefill included (a chunk hands its tail to the next),
    with and without `step_join`, token for token with `generate`."""
    from pipeedge_tpu.parallel.batcher import ContinuousBatcher
    _, _, pipe, ids, _ = tiny
    prompts = [ids[:1, :7], ids[1:, :13], ids[:1, 5:10]]
    batcher = ContinuousBatcher(pipe, max_active=2, chunk_tokens=4,
                                step_join=step_join)
    for rid, prompt in enumerate(prompts):
        batcher.submit(rid, prompt, new_tokens=5)
    results = batcher.run()
    for rid, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            results[rid], np.asarray(pipe.generate(prompt, 5)))


def test_tools_generate_takes_the_model_and_its_cut(capsys, monkeypatch):
    import sys
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import generate
    from pipeedge_tpu import utils
    # (the CLI's persistent compile cache is the process's: not a test's)
    monkeypatch.setattr(utils, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "generate.py", "-m", TINY + "@5", "-b", "2", "--prompt-len", "13",
        "--new-tokens", "4", "--max-len", "32"])
    generate.main()
    assert "tokens" in capsys.readouterr().out


@pytest.mark.parametrize("asked", ["mesh", "sp_mesh", "ep_mesh",
                                   "tp_ep_mesh", "cache_bits", "forward",
                                   "kv_pages", "speculative"])
def test_what_the_family_cannot_do_is_refused_by_name(asked):
    from jax.sharding import Mesh
    entry = registry.get_model_entry(TINY)
    _, params, stage = registry.module_shard_factory(TINY, None, 1, 32,
                                                     unroll=False)
    if asked == "forward":
        with pytest.raises(NotImplementedError, match="runs of"):
            shard_apply(entry.family.FAMILY, entry.config, stage, params,
                        jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(NotImplementedError, match="lfm2"):
            lfm2.FAMILY.sublayer({}, 0, None, entry.config)
        return
    if asked in ("kv_pages", "speculative"):
        pipe = decode.DecodePipeline(entry.family.FAMILY, entry.config,
                                     [(1, 32)], [params], max_len=32)
    if asked == "kv_pages":
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import serve
        with pytest.raises(NotImplementedError, match="lfm2"):
            serve._Service(pipe, kv_pages=4)
        return
    if asked == "speculative":
        from pipeedge_tpu.parallel.speculative import SpeculativeDecoder
        draft = decode.build_decode_pipeline("pipeedge/test-tiny-gpt2", None,
                                             max_len=32)
        for target, drafter in ((pipe, draft), (draft, pipe)):
            with pytest.raises(NotImplementedError,
                               match="lfm2.*conv_tail.*earlier position"):
                SpeculativeDecoder(target, drafter)
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
        decode.DecodePipeline(entry.family.FAMILY, entry.config, [(1, 32)],
                              [params], max_len=32, **option)


def test_the_cells_cut_is_a_decoder_the_clis_take():
    assert registry.decoder_model(CELL) == CELL
    assert registry.decoder_model("LiquidAI/LFM2-8B-A1B") \
        == "LiquidAI/LFM2-8B-A1B"
    entry = registry.get_model_entry(CELL)
    cfg = entry.config
    assert (entry.layers, cfg.num_hidden_layers, cfg.held_experts,
            cfg.n_experts, cfg.vocab_size) == (48, 12, (), 32, 65536)
    whole = registry.get_model_config("LiquidAI/LFM2-8B-A1B")
    assert [i for i in range(24)
            if whole.layer_types[i] == "full_attention"] \
        == [2, 6, 10, 14, 18, 21]           # no interval: 21, not 22
    assert list(whole.layer_types) == _config(tiny=False)["layer_types"]
    assert [lfm2.block_kind(cfg, i) for i in range(12)] == [
        "conv_dense", "conv_dense", "attn_experts", "conv_experts",
        "conv_experts", "conv_experts", "attn_experts", "conv_experts",
        "conv_experts", "conv_experts", "attn_experts", "conv_experts"]
    assert 512 % cfg.prefill_chunk == 0       # the cell's prompt, in spans
    # every parameter of the cut, by the loader's shapes: 3.929 G in the
    # file, and the table a second time as the head on the chip
    stage = ShardConfig(1, 48, is_first=True, is_last=True)
    params = jax.eval_shape(lambda: lfm2._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert count - 65536 * 2048 \
        == costs.held_parameters(_config(tiny=False)) == 3928728256


# -- the benchmark's cost functions --------------------------------------------

@pytest.mark.parametrize("size", ["tiny", "published"])
def test_costs_against_hand_counts(size):
    config = _config(tiny=size == "tiny")
    if size == "tiny":
        # in_proj 32 x 96, kernel 32 x 3, out 32 x 32
        assert costs.conv_params(config) == 3072 + 96 + 1024 == 4192
        # q and out 32 x 32, k and v 32 x 16, two norms of 8
        assert costs.attention_params(config) == 2048 + 1024 + 16 == 3088
        assert costs.dense_ffn_params(config) == 3 * 32 * 64
        assert costs.expert_params(config) == 3 * 32 * 16
        assert costs.router_params(config) == 256 + 8
        fixed = 6 * 4192 + 2 * 3088 + 8 * 64 + 2 * 6144 + 6 * 264 + 32
        assert costs.held_parameters(config) \
            == fixed + 6 * 8 * 1536 + 32 * 100
        assert costs.kv_bytes_a_token(config) == 2 * 2 * 16 * 4
        assert costs.tail_bytes_a_row(config) == 6 * 2 * 32 * 4
        products = 2 * (6 * 4096 + 2 * 3072 + 2 * 6144
                        + 6 * (256 + 2 * 1536)) + 6 * 8 * 32
        assert costs.token_product_flops(config) == products
        assert costs.pair_flops(config) == 4 * 4 * 8
        assert costs.prefill_flops(config, 2, 20) == 2 * (
            20 * products + 2 * 128 * 210 + 2 * 32 * 100)
        assert costs.decode_step_flops(config, 2, 21) == 2 * (
            products + 2 * 128 * 21 + 2 * 32 * 100)
        assert costs.decode_step_bytes(config, 2, 21, 1.5) == 2 * (
            fixed + 6 * 1.5 * 1536 + 32 * 100) + 2 * (21 * 256 + 2 * 1536)
        assert costs.prefill_bytes(config, 2, 20) == 2 * (
            fixed + 6 * 8 * 1536 + 32 * 100) + 2 * (20 * 256 + 1536)
        return
    # ISSUE 37's arithmetic: a convolution mixer 16.78 M, an attention
    # 10.49 M, a dense FFN 44.04 M, an expert 11.01 M x 32 = 352.3 M and a
    # router 0.07 M, the table 134.2 M: 3.93 G parameters, 7.86 GB
    assert costs.conv_params(config) == 12582912 + 6144 + 4194304 == 16783360
    assert costs.attention_params(config) == 2 * 4194304 + 2 * 1048576 + 128
    assert costs.dense_ffn_params(config) == 44040192
    assert costs.expert_params(config) == 11010048
    assert costs.held_parameters(config) == 9 * 16783360 + 3 * 10485888 \
        + 12 * 4096 + 2 * 44040192 + 10 * (65568 + 32 * 11010048) \
        + 134217728 + 2048 == 3928728256
    # 0.71 G parameters a token in 12 layers, 1.42 GFLOP; a pair 8,192
    assert 1.41e9 < costs.token_product_flops(config) < 1.43e9
    assert costs.pair_flops(config) == 8192
    # a prefill of the cell 94 TFLOP; a step with every expert touched reads
    # 7.86 GB of weights, 1.2 GB of a 768-position window and 38 MB of tails
    # for 0.22 TFLOP (the head's 0.27 GFLOP a row among them)
    assert 9.3e13 < costs.prefill_flops(config, 128, 512) < 9.5e13
    assert costs.weight_bytes(config, 32) == 2 * 3928728256
    assert 9.0e9 < costs.decode_step_bytes(config, 128, 768, 32) < 9.2e9
    assert 2.1e11 < costs.decode_step_flops(config, 128, 768) < 2.25e11
