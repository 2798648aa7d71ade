"""The laguna family (models/laguna.py: window layers that keep a ring of the
last `sliding_window` positions beside full layers that keep the context in
one stage's cache, query heads and rotation by the kind of layer, a dense
FFN in the leading block and a softmax-routed expert layer with a gated
shared expert after) against the benchmark's plain reference, on the CPU at
`pipeedge/test-tiny-laguna`, with seeded weights in the published key
scheme; and the ring leaf of parallel/decode.py on its own."""
import collections
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_laguna as costs, weights
from benchmark.reference import laguna as reference
from pipeedge_tpu.models import ShardConfig, laguna, registry, stage_cache
from pipeedge_tpu.models.layers import rope_frequencies, yarn_frequencies
from pipeedge_tpu.models.shard import (BlockRuns, CacheLeaf, kind_runs,
                                       shard_apply)
from pipeedge_tpu.parallel import decode
from pipeedge_tpu.telemetry import metrics as prom
from test_lfm2 import _equations

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "pipeedge/test-tiny-laguna"
WHOLE = "poolside/Laguna-XS.2"
CELL = WHOLE + "@5"
LENGTH, MAX_LEN = 44, 48    # five and a half of the tiny model's windows


def _config(tiny=True, **over):
    name = "laguna-xs.2.json"
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
    """The benchmark's tiny cut, six blocks in one stage: (config, weights
    file, pipeline, ids [2, 44], reference logits)."""
    config = _config()
    path = weights.write(config, 2 ** 31 + 7, str(
        tmp_path_factory.mktemp("laguna") / "weights.npz"))
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=MAX_LEN, dtype=jnp.float32,
        model_file=path)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)
    return config, path, pipe, ids, wanted


# float32 program against float32 reference: they differ by the order of
# their sums (a ring's slots against the whole sequence under a mask, a KV
# group at a time against a head at a time, the experts' tiles against an
# expert at a time; 1.8e-7 of the logits' range measured); 1e-5 leaves room
# for another BLAS and would fail a bfloat16 product or a slot read at the
# wrong position a hundred times over
TOLERANCE = 1e-5


def _close(got, wanted):
    return np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


# the tiny model prefills in spans of 4 into rings of 8: a prompt shorter
# than a span (1, 3), a span (4), not whole spans (7, 13, 27, 30), past one
# window (13), past three (27, 30): the steps after each run to position 43,
# so every ring wraps at least once more
@pytest.mark.parametrize("prompt_len", [1, 3, 4, 7, 13, 27, 30])
def test_spans_then_decode_match_the_reference(prompt_len, tiny):
    _, _, pipe, ids, wanted = tiny
    got = _logits_through_the_cache(pipe, ids, prompt_len)
    assert _close(got, wanted[:, prompt_len - 1:])


@pytest.fixture(scope="module")
def cut(tiny):
    """`<name>@5` over the same file (a loader reads its own layers' keys):
    the cell's cut, the dense full block, a period's three window blocks
    and a routed full block."""
    _, path, _, _, _ = tiny
    return decode.build_decode_pipeline(TINY + "@5", None, max_len=MAX_LEN,
                                        dtype=jnp.float32, model_file=path)


def test_a_cut_in_depth_matches_the_reference(tiny, cut):
    _, path, _, ids, _ = tiny
    assert cut.stages[0]["runs"] == (("full_dense", 1), ("sliding_routed", 3),
                                     ("full_routed", 1))
    with np.load(path) as tensors:
        wanted = reference.forward(_config(num_hidden_layers=5), tensors,
                                   ids)[:, 20:]
    assert _close(_logits_through_the_cache(cut, ids, 21), wanted)


def test_spans_that_straddle_a_rings_end_are_written_around_it(tiny):
    """Spans at odd positions (a prefix's suffix, a chunked prompt): 5 rows
    at 0, 6 at 5 (slots 5, 6, 7, 0, 1, 2), 7 at 11, 3 at 18, 8 at 21 (a
    whole ring's worth, from slot 5), 2 at 29, 6 at 31: every logit of every
    span is the whole-sequence reference's."""
    _, _, pipe, ids, wanted = tiny
    before = _counters()["swa_ring_wraps", "decode"] \
        + _counters()["swa_ring_wraps", "prefill"]
    caches, pos = pipe._fresh_caches(2), 0
    for span in (5, 6, 7, 3, 8, 2, 6):
        data, caches = pipe.extend(ids[:, pos:pos + span], caches, pos)
        assert _close(np.asarray(data), wanted[:, pos:pos + span]), pos
        pos += span
    # the stage's four window blocks, the calls at 5, 11, 21 and 31 (the one
    # at 18 ends at slot 4, the one at 29 at 6): counted on the device
    pipe._count([c["stats"] * 0 for c in caches], caches)
    after = _counters()["swa_ring_wraps", "decode"] \
        + _counters()["swa_ring_wraps", "prefill"]
    assert after - before == 4 * 4


def test_a_whole_prompt_prefill_is_the_spans(tiny):
    """The served path's prefill program takes the whole prompt in one
    call, 21 rows into rings of 8: the window is a mask among the call's
    own rows, the rings are left with the last 8, and the full leaves and
    the logits are what the spans give."""
    _, _, pipe, ids, wanted = tiny
    stage = pipe.stages[0]
    data, cache = stage["prefill"](stage["params"],
                                   jnp.asarray(ids[:, :21], jnp.int32),
                                   pipe._fresh_caches(2)[0])
    _, spans = pipe._prefill(jnp.asarray(ids[:, :21], jnp.int32))
    assert _close(np.asarray(data), wanted[:, :21])
    for name in ("k", "v", "k_ring", "v_ring"):
        np.testing.assert_allclose(cache[name], spans[0][name], atol=1e-5)


def test_bfloat16_weights_are_computed_on_in_float32(tiny):
    """The seeded values are ones a bfloat16 holds, so the program's
    bfloat16 weights are the reference's float32 ones, and its float32
    activations and cache over them give the reference's logits."""
    config, path, _, ids, wanted = tiny
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=MAX_LEN, dtype=jnp.bfloat16,
        model_file=path)
    blocks = pipe.stages[0]["params"]["blocks"]
    assert all(leaf.dtype == jnp.bfloat16
               for leaf in jax.tree_util.tree_leaves(blocks))
    cache = pipe._fresh_caches(2)[0]
    assert {cache[name].dtype for name in ("k", "v", "k_ring", "v_ring")} \
        == {jnp.dtype(jnp.float32)}
    assert _close(_logits_through_the_cache(pipe, ids, 27), wanted[:, 26:])


# -- the ring leaf on its own (parallel/decode.py) -----------------------------

def _rows(rng, layers, batch, span, width):
    return jnp.asarray(rng.normal(size=(layers, batch, span, width)),
                       jnp.float32)


@pytest.mark.parametrize("ring, calls", [
    (8, [(0, 1), (1, 1), (2, 5), (7, 1), (8, 1), (9, 6), (15, 8), (23, 3)]),
    (8, [(0, 4), (4, 4), (8, 4), (12, 4), (16, 1), (17, 1)]),
    (8, [(0, 21), (21, 1), (22, 7)]),       # a prompt longer than the ring
    (5, [(0, 3), (3, 4), (7, 5), (12, 2)]),
])
def test_rows_are_written_where_they_fall_in_a_ring(ring, calls):
    """`write_rows` against the rule itself: position p at slot p mod W,
    a later row over an earlier one, every other slot as it was."""
    rng = np.random.default_rng(ring)
    cache = {"k_ring": jnp.asarray(rng.normal(size=(2, 3, ring, 4)),
                                   jnp.float32)}
    wanted = np.array(cache["k_ring"])
    write = jax.jit(lambda cache, rows, pos: stage_cache.write_rows(
        cache, rows, pos, rings=("k_ring",)))
    for pos, span in calls:
        rows = _rows(rng, 2, 3, span, 4)
        cache = write(cache, {"k_ring": rows}, pos)
        for i in range(span):
            wanted[:, :, (pos + i) % ring] = np.asarray(rows[:, :, i])
        np.testing.assert_array_equal(cache["k_ring"], wanted)


@pytest.mark.parametrize("calls", [
    [(0, 3), (3, 1), (4, 6), (10, 1), (11, 7), (18, 8), (26, 1), (27, 2)],
    [(0, 4), (4, 4), (8, 4), (12, 4), (16, 1), (17, 1), (18, 1)],
    [(0, 1)] + [(p, 1) for p in range(1, 20)],
])
def test_the_ring_gives_the_numbers_of_the_window_mask_over_a_full_leaf(
        calls):
    """The Llama family's `window=` mask over a leaf of `max_len` positions
    and a ring of `window` positions, fed the same rows call after call:
    `attend` over what each read hands back gives the same contexts (the
    kept keys are the same set; a ring's sit in another order)."""
    window, max_len, groups, hd = 8, 32, 2, 4
    cfg = registry.get_model_config(TINY)
    rng = np.random.default_rng(len(calls))
    caches = {False: {"k": jnp.zeros((1, 2, max_len, groups * hd)),
                      "v": jnp.zeros((1, 2, max_len, groups * hd))},
              True: {"k": jnp.zeros((1, 2, window, groups * hd)),
                     "v": jnp.zeros((1, 2, window, groups * hd))}}
    for pos, span in calls:
        q = jnp.asarray(rng.normal(size=(2, span, 6, hd)), jnp.float32)
        k, v = (jnp.asarray(rng.normal(size=(2, span, groups, hd)),
                            jnp.float32) for _ in range(2))
        got, kept = {}, {}
        for ring, cache in caches.items():
            ks, vs, keeps, bcache = stage_cache.cache_update_and_read(
                stage_cache.LayerCache(cache, jnp.int32(0)), k, v, pos, False,
                span, jnp.float32, read_len=max_len, window=window,
                ring=ring)
            got[ring] = np.asarray(stage_cache.attend(q, ks, vs, keeps, cfg))
            rows = {name: row[None] for name, row in bcache.rows.items()}
            caches[ring] = stage_cache.write_rows(
                cache, rows, pos, rings=("k", "v") if ring else ())
            kept[ring] = int(keeps[0].sum())
        np.testing.assert_allclose(got[True], got[False], atol=2e-6)
        # as many cached positions kept, whatever slots hold them
        assert kept[True] == kept[False]


def test_a_slot_is_kept_by_the_position_it_holds():
    """Before a call at `pos`, slot s holds the largest p < pos with p mod
    W == s; query q keeps it where q - W < p."""
    window = 8
    cache = {"k": jnp.zeros((1, 1, window, 4)),
             "v": jnp.zeros((1, 1, window, 4))}
    k = v = jnp.zeros((1, 3, 1, 4))
    for pos in (0, 1, 5, 8, 9, 21):
        keep = np.asarray(stage_cache.cache_update_and_read(
            stage_cache.LayerCache(cache, jnp.int32(0)), k, v, pos, False, 3,
            jnp.float32, window=window, ring=True)[2][0])
        for i in range(3):
            for slot in range(window):
                held = [p for p in range(pos) if p % window == slot][-1:]
                assert keep[i, slot] == bool(
                    held and held[0] > pos + i - window), (pos, i, slot)


@pytest.mark.parametrize("size", ["tiny", "tiny@5", "published"])
def test_a_fresh_cache_holds_each_leaf_at_its_own_length(size):
    model, rows, max_len = {"tiny": (TINY, 2, MAX_LEN),
                            "tiny@5": (TINY + "@5", 2, MAX_LEN),
                            "published": (CELL, 32, 8192)}[size]
    entry = registry.get_model_entry(model)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len,
        leaves=laguna.cache_leaves(cfg),
        runs=kind_runs(laguna.FAMILY, cfg, stage)))
    full = sum(kind == "full_attention"
               for kind in cfg.layer_types[:cfg.num_hidden_layers])
    width = cfg.kv_heads * cfg.head_dim
    for name in ("k", "v"):
        assert cache[name].shape == (full, rows, max_len, width)
    for name in ("k_ring", "v_ring"):
        assert cache[name].shape == (cfg.num_hidden_layers - full, rows,
                                     cfg.sliding_window, width)
    assert cache["stats"].shape == (cfg.num_hidden_layers,
                                    len(laguna.STATS), 2)
    if size == "published":     # ISSUE 40's bytes: 4.29 GB and 0.40 GB
        sizes = {name: leaf.size * 4 for name, leaf in cache.items()}
        assert sizes["k"] + sizes["v"] == 4294967296
        assert sizes["k_ring"] + sizes["v_ring"] == 402653184
        config = _config(tiny=False)
        assert rows * max_len * costs.kv_bytes_a_token(config) \
            == sizes["k"] + sizes["v"]
        assert rows * costs.ring_bytes_a_row(config) \
            == sizes["k_ring"] + sizes["v_ring"]


def test_a_ring_is_no_longer_than_the_stage(tiny):
    """`max_len` under the window: the ring keeps `max_len` positions, and
    the logits are the reference's still."""
    _, path, _, ids, wanted = tiny
    pipe = decode.build_decode_pipeline(TINY, None, max_len=6,
                                        dtype=jnp.float32, model_file=path)
    assert pipe._fresh_caches(1)[0]["k_ring"].shape[2] == 6
    data, _ = pipe._prefill(jnp.asarray(ids[:, :6], jnp.int32))
    assert _close(np.asarray(data[:, -1]), wanted[:, 5])


def test_the_gauge_says_what_each_leaf_takes(tiny):
    _, _, pipe, _, _ = tiny
    pipe._fresh_caches(3)
    gauge = prom.REGISTRY.gauge("pipeedge_cache_leaf_bytes", "")
    assert gauge.value(leaf="k") == 2 * 3 * MAX_LEN * 32 * 4
    assert gauge.value(leaf="k_ring") == 4 * 3 * 8 * 32 * 4


def test_a_span_longer_than_the_ring_is_refused_at_construction():
    entry = registry.get_model_entry(TINY)
    _, params, _ = registry.module_shard_factory(TINY, None, 1, 24,
                                                 unroll=False)
    cfg = dataclasses.replace(entry.config, prefill_chunk=16)
    with pytest.raises(ValueError, match="spans of 16.*k_ring.*ring of 8"):
        decode.DecodePipeline(laguna.FAMILY, cfg, [(1, 24)], [params],
                              max_len=MAX_LEN)


@pytest.mark.parametrize("model, widths", [(TINY, 2), (CELL, 2), (WHOLE, 2)])
def test_a_job_asks_for_two_widths_an_octave_where_few_blocks_follow_it(
        model, widths):
    """Two of the cut's five blocks (10 of the model's 40, 2 of the tiny
    model's 6) read the ladder's window; a ring's read theirs whatever it
    says, and count with those that keep a state."""
    entry = registry.get_model_entry(model)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    stages = [{"runs": kind_runs(laguna.FAMILY, entry.config, stage)}]
    leaves = laguna.cache_leaves(entry.config)
    assert decode.job_per_octave(leaves, stages) == widths
    # the same blocks with their window as a mask over full-length leaves
    # would all follow the ladder
    plain = {name: leaf._replace(length=0) if isinstance(leaf, CacheLeaf)
             else leaf for name, leaf in leaves.items()}
    assert decode.job_per_octave(plain, stages) == decode.JOB_PER_OCTAVE


# what the lfm2 family's tiny step (span 1) and span (8) programs traced to
# before the ring leaf, `Window`, `rotate_halves` and the move of
# `yarn_frequencies` (the parent commit): equations at the top level and in
# all. gpt2's, keye's, kimi's and qwen3_next's are held to the same parent's
# counts by `tests/test_lfm2.py`. Since PR 41 nine more a traced expert
# layer there and here: its fourth count and the way back's select
TRACED = {("pipeedge/test-tiny-lfm2", 1): (44, 1884),
          ("pipeedge/test-tiny-lfm2", 8): (44, 1884)}


@pytest.mark.parametrize("model, span", sorted(TRACED))
def test_the_fifth_family_traces_to_what_it_traced_to(model, span):
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


# -- heads and rotation by the kind of layer -----------------------------------

def test_query_heads_are_a_layers_kind():
    cfg = registry.get_model_config(WHOLE)
    config = _config(tiny=False)
    assert list(cfg.layer_heads) == config["num_attention_heads_per_layer"]
    assert list(cfg.layer_types) == config["layer_types"]
    assert (laguna.heads_of(cfg, "full"), laguna.heads_of(cfg, "sliding")) \
        == (48, 64)
    assert [laguna.block_kind(cfg, i) for i in range(5)] == [
        "full_dense", "sliding_routed", "sliding_routed", "sliding_routed",
        "full_routed"]
    tiny = registry.get_model_config(TINY)
    assert (laguna.heads_of(tiny, "full"), laguna.heads_of(tiny, "sliding")) \
        == (4, 6)
    mixed = dataclasses.replace(tiny, layer_heads=(4, 6, 6, 4, 4, 6))
    with pytest.raises(ValueError, match="one count a kind"):
        laguna.heads_of(mixed, "sliding")


def test_a_program_built_with_the_wrong_head_count_fails(tiny):
    """Blocks built for 6 full and 4 sliding heads under a configuration
    that says 4 and 6: the block step reads the count off its `q` leaf and
    holds it to its kind's."""
    _, _, pipe, ids, _ = tiny
    entry = registry.get_model_entry(TINY)
    swapped = dataclasses.replace(
        entry.config, layer_heads=tuple(
            {4: 6, 6: 4}[heads] for heads in entry.config.layer_heads))
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    params = laguna.init_params(swapped, stage)
    wrong = decode.DecodePipeline(laguna.FAMILY, entry.config, [(1, 24)],
                                  [params], max_len=MAX_LEN)
    with pytest.raises(ValueError, match="full block of 4 query heads of 16 "
                       "was built with q_proj"):
        wrong.extend(ids[:, :4], wrong._fresh_caches(2), 0)
    # and a file of another count is refused by its key
    _, path, _, _, _ = tiny
    with np.load(path) as tensors:
        with pytest.raises(ValueError, match="q_proj.weight"):
            laguna.load_params(swapped, stage, tensors)


# the published full layers' 32 frequencies under YaRN, by hand from the
# formula: f_i = 500000**(-2i/64); d(n) = 64 ln(4096 / (2 pi n)) / (2 ln
# 500000): d(64) = 5.65 -> low 5, d(1) = 15.8 -> high 16; i <= 5 stay, 6..15
# are f_i (1 - (i - 5) / 11 * 63 / 64), i >= 16 are f_i / 64
def test_yarn_frequencies_and_the_factor_are_the_published_numbers():
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    share = np.ones(32)
    share[6:16] = 1 - (np.arange(6, 16) - 5) / 11 * 63 / 64
    share[16:] = 1 / 64
    np.testing.assert_allclose(share[[5, 6, 10, 15, 16]], [
        1.0, 0.91051136, 0.55255682, 0.10511364, 0.015625], rtol=1e-7)
    cfg = registry.get_model_config(WHOLE)
    freqs = laguna.full_frequencies(cfg)
    assert freqs.shape == (32,)             # half of a head's 128 lanes turn
    np.testing.assert_allclose(freqs, plain * share, rtol=2e-6)
    np.testing.assert_allclose(
        freqs, yarn_frequencies(64, 500000.0, 64.0, 4096, 64.0, 1.0))
    assert cfg.rope_yarn[4] == 1.4158883083359672
    assert abs(cfg.rope_yarn[4] - (0.1 * np.log(64.0) + 1)) < 1e-12
    rope = _config(tiny=False)["rope_parameters"]
    wanted, scale = reference.frequencies(rope["full_attention"], 128)
    np.testing.assert_allclose(wanted, freqs, rtol=1e-7)
    assert scale == cfg.rope_yarn[4]
    # the window layers: plain, base 10,000, all 128 lanes
    wanted, scale = reference.frequencies(rope["sliding_attention"], 128)
    np.testing.assert_allclose(wanted, rope_frequencies(128, 10000.0),
                               rtol=1e-7)
    assert scale == 1.0 and cfg.sliding_rope_theta == 10000.0
    # the tiny model's ramp has a frequency halfway
    tiny = registry.get_model_config(TINY)
    np.testing.assert_allclose(
        laguna.full_frequencies(tiny) / rope_frequencies(8, 10000.0),
        [1.0, 0.625, 0.25, 0.25], rtol=1e-6)


@pytest.mark.parametrize("sliding", [False, True])
def test_each_kind_turns_its_own_lanes(sliding):
    cfg = registry.get_model_config(TINY)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 3, 16)),
                    jnp.float32)
    pos = jnp.arange(30, 35)
    rope = _config()["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"]
    freqs, scale = reference.frequencies(rope, 16)
    angles = np.asarray(pos, np.float32)[:, None] * freqs[None]
    wanted = jnp.stack([reference._rotate(row, jnp.asarray(angles), scale)
                        for row in x])
    got = laguna.rotate(x, pos, cfg, sliding)
    np.testing.assert_allclose(got, wanted, atol=1e-6)
    if not sliding:     # lanes [8, 16) stay, and the turned ones carry 1.1386
        np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
        np.testing.assert_allclose(
            jnp.linalg.norm(got[..., :8], axis=-1),
            1.1386294361119891 * jnp.linalg.norm(x[..., :8], axis=-1),
            rtol=1e-5)


# -- the loader ----------------------------------------------------------------

def test_the_loader_reads_the_published_keys_into_init_params_shapes(tiny):
    config, path, _, _, _ = tiny
    entry = registry.get_model_entry(TINY)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    with np.load(path) as tensors:
        keys = set(tensors.files)
        loaded = laguna.load_params(entry.config, stage, tensors)
        gate = np.asarray(tensors["model.layers.3.mlp.gate.weight"])
        shapes = {key: tensors[key].shape for key in (
            "model.layers.0.self_attn.q_proj.weight",
            "model.layers.1.self_attn.q_proj.weight",
            "model.layers.1.self_attn.o_proj.weight",
            "model.layers.1.self_attn.g_proj.weight")}
    drawn = laguna.init_params(entry.config, stage)
    both = jax.tree_util.tree_map(lambda leaf: (leaf.shape, leaf.dtype),
                                  (loaded, drawn))
    assert both[0] == both[1]
    assert isinstance(loaded["blocks"], BlockRuns)
    assert len(loaded["blocks"].runs) == 4
    assert shapes == {
        "model.layers.0.self_attn.q_proj.weight": (4 * 16, 32),
        "model.layers.1.self_attn.q_proj.weight": (6 * 16, 32),
        "model.layers.1.self_attn.o_proj.weight": (32, 6 * 16),
        "model.layers.1.self_attn.g_proj.weight": (6, 32)}
    for key in ("model.embed_tokens.weight", "model.norm.weight",
                "lm_head.weight", "model.layers.0.mlp.gate_proj.weight",
                "model.layers.5.self_attn.k_norm.weight",
                "model.layers.5.mlp.experts.7.down_proj.weight",
                "model.layers.2.mlp.shared_expert.up_proj.weight",
                "model.layers.2.mlp.shared_expert_gate.weight"):
        assert key in keys
    # three tables and norms, 6 layers x (2 norms + 7 attention), a dense
    # FFN, 5 x (router + 8 experts + shared expert + its gate)
    assert len(keys) == 3 + 6 * 9 + 3 + 5 * (1 + 8 * 3 + 3 + 1)
    np.testing.assert_array_equal(gate[1::2], -gate[0::2])


def test_a_tensor_of_another_shape_is_refused_by_its_key(tiny):
    _, path, _, _, _ = tiny
    entry = registry.get_model_entry(TINY)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    with np.load(path) as tensors:
        wider = dataclasses.replace(entry.config, moe_intermediate_size=24)
        with pytest.raises(ValueError,
                           match=r"experts\.0\.gate_proj\.weight"):
            laguna.load_params(wider, stage, tensors)


# -- the expert layer ----------------------------------------------------------

def test_the_expert_layer_is_the_scaled_softmax_top_k_and_a_gated_shared_one(
        tiny):
    """`s = softmax(router u)`, the 2 largest of 8, renormalised, times 2.5;
    plus `sigmoid(shared_expert_gate u)` times the shared expert, which the
    factor does not scale: against a plain loop over the block's leaves."""
    from pipeedge_tpu.models.decoder import routed_experts
    _, _, pipe, _, _ = tiny
    cfg = pipe.cfg
    run = pipe.stages[0]["params"]["blocks"].runs[1]     # three routed blocks
    block = jax.tree_util.tree_map(lambda leaf: leaf[1], run)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 7, 32)),
                    jnp.float32)
    got, stats = routed_experts(block, x, cfg)
    assert (cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.router,
            cfg.num_experts_per_tok) == (2.5, True, "softmax", 2)

    def swiglu(u, w):
        return (jax.nn.silu(u @ w["gate"].T) * (u @ w["up"].T)) @ w["down"].T

    tokens = x.reshape(-1, 32)
    scores = jax.nn.softmax(tokens @ block["router"]["w"], -1)
    top, chosen = jax.lax.top_k(scores, 2)
    gates = top / top.sum(-1, keepdims=True) * 2.5
    wanted = jax.nn.sigmoid(tokens @ block["shared_gate"].T) \
        * swiglu(tokens, block["shared"])
    for slot in range(2):
        for token in range(tokens.shape[0]):
            e = int(chosen[token, slot])
            one = {name: leaf[e] for name, leaf in block["experts"].items()}
            wanted = wanted.at[token].add(
                gates[token, slot] * swiglu(tokens[token], one))
    np.testing.assert_allclose(got.reshape(-1, 32), wanted, atol=1e-6)
    assert int(stats[0]) == 14 * 2


# -- counters ------------------------------------------------------------------

def _counters():
    return {(name, phase): prom.REGISTRY.counter(
        f"pipeedge_{name}_total", "").value(phase=phase)
        for name in laguna.STATS for phase in ("prefill", "decode")}


def test_counters_of_one_batch_are_what_its_sizes_predict(tiny):
    _, _, pipe, ids, _ = tiny
    before = _counters()
    pipe.generate(ids[:, :21], 8)
    gained = {key: value - before[key] for key, value in _counters().items()}
    # the four window blocks, 2 rows, window 8. Prefill in spans of 4, 4, 4,
    # 4, 4 and 1 at 0, 4, .., 20: each reads its ring (8 slots a query) and
    # its own pairs (span a query)
    reads = sum(span * (8 + span) for span in (4, 4, 4, 4, 4, 1))
    assert gained["swa_positions_read", "prefill"] == 4 * 2 * reads
    # query t keeps min(t + 1, 8) positions
    assert gained["swa_positions_live", "prefill"] \
        == 4 * 2 * sum(min(t + 1, 8) for t in range(21))
    assert gained["swa_ring_wraps", "prefill"] == 0    # spans of 4 in 8
    # seven steps at 21..27: the ring and itself, all nine read, eight kept
    assert gained["swa_positions_read", "decode"] == 4 * 2 * 7 * 9
    assert gained["swa_positions_live", "decode"] == 4 * 2 * 7 * 8
    assert gained["swa_ring_wraps", "decode"] == 0     # one row never does
    # five routed layers of six; every expert held: 2 a token a layer
    assert gained["moe_layer_calls", "prefill"] == 6 * 5
    assert gained["moe_layer_calls", "decode"] == 7 * 5
    assert gained["moe_assignments", "prefill"] == 2 * 21 * 5 * 2
    assert gained["moe_assignments", "decode"] == 2 * 7 * 5 * 2
    assert 0 < gained["moe_experts_touched", "decode"] <= 7 * 5 * 4


def test_the_live_share_is_what_the_benchmarks_reader_reads(tiny):
    import importlib.util
    spec = importlib.util.spec_from_file_location("swa_reader", os.path.join(
        REPO, "benchmark", "metrics", "swa_live_share.laguna-repo.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    _, _, pipe, ids, _ = tiny
    pipe.generate(ids[:, :27], 4)
    share = module.read({})
    counts = _counters()
    live = sum(counts["swa_positions_live", phase]
               for phase in ("prefill", "decode"))
    read = sum(counts["swa_positions_read", phase]
               for phase in ("prefill", "decode"))
    assert share == pytest.approx(100.0 * live / read) and 50 < share < 100


# -- what it runs, and what it refuses by name ----------------------------------

def test_a_prefix_leaves_the_rings_its_continuation_needs(tiny):
    """A handle's rings hold the prefix's last 8 positions wherever it
    ended (13: slots 5, 6, 7, 0, ..): the suffix's span straddles the ring's
    end, rows broadcast over the batch, and the tokens are `generate`'s."""
    _, _, pipe, ids, _ = tiny
    whole = np.asarray(pipe.generate(ids[:, :27], 6))
    handle = pipe.precompute_prefix(ids[0, :13])
    suffix = np.repeat(ids[:1, 13:27], 2, axis=0)
    got = np.asarray(pipe.generate(suffix, 6, prefix=handle))
    for row in got:
        np.testing.assert_array_equal(row[14:], whole[0, 27:])


def test_a_handle_from_a_pipeline_of_other_leaves_is_refused(tiny):
    _, _, pipe, ids, _ = tiny
    other = decode.build_decode_pipeline("pipeedge/test-tiny-lfm2", None,
                                         max_len=MAX_LEN)
    handle = other.precompute_prefix(np.arange(5))
    with pytest.raises(ValueError, match="incompatible pipeline") as caught:
        pipe.generate(ids[:, :4], 2, prefix=handle)
    assert "k_ring" in str(caught.value) \
        and "conv_tail" in str(caught.value)
    assert pipe._prefix_sig() == decode.build_decode_pipeline(
        TINY, None, max_len=MAX_LEN)._prefix_sig()


@pytest.mark.parametrize("step_join", [False, True])
def test_the_dense_served_path_runs_it(step_join, tiny):
    """`tools/serve.py` without pages: the wave batcher over per-request
    caches, whole-prompt and chunked prefill included (a ring is a position
    leaf that happens to be short), with and without `step_join`, token for
    token with `generate`."""
    from pipeedge_tpu.parallel.batcher import ContinuousBatcher
    _, _, pipe, ids, _ = tiny
    prompts = [ids[:1, :7], ids[1:, :21], ids[:1, 5:18]]
    batcher = ContinuousBatcher(pipe, max_active=2, chunk_tokens=4,
                                step_join=step_join)
    for rid, prompt in enumerate(prompts):
        batcher.submit(rid, prompt, new_tokens=12)
    results = batcher.run()
    for rid, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            results[rid], np.asarray(pipe.generate(prompt, 12)))


def test_a_whole_prompt_through_the_executor_is_generates_tokens(tiny):
    from pipeedge_tpu.parallel.batcher import ContinuousBatcher
    _, _, pipe, ids, _ = tiny
    batcher = ContinuousBatcher(pipe, max_active=1)
    batcher.submit(0, ids[:1, :21], new_tokens=10)
    np.testing.assert_array_equal(
        batcher.run()[0], np.asarray(pipe.generate(ids[:1, :21], 10)))


def test_tools_generate_takes_the_model_and_its_cut(capsys, monkeypatch):
    import sys
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import generate
    from pipeedge_tpu import utils
    # (the CLI's persistent compile cache is the process's: not a test's)
    monkeypatch.setattr(utils, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "generate.py", "-m", TINY + "@5", "-b", "2", "--prompt-len", "27",
        "--new-tokens", "4", "--max-len", "40"])
    generate.main()
    assert "tokens" in capsys.readouterr().out


@pytest.mark.parametrize("asked", ["mesh", "sp_mesh", "ep_mesh",
                                   "tp_ep_mesh", "cache_bits", "forward",
                                   "kv_pages", "speculative"])
def test_what_the_family_cannot_do_is_refused_by_name(asked):
    from jax.sharding import Mesh
    entry = registry.get_model_entry(TINY)
    _, params, stage = registry.module_shard_factory(TINY, None, 1, 24,
                                                     unroll=False)
    if asked == "forward":
        with pytest.raises(NotImplementedError, match="runs of"):
            shard_apply(entry.family.FAMILY, entry.config, stage, params,
                        jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(NotImplementedError, match="laguna"):
            laguna.FAMILY.sublayer({}, 0, None, entry.config)
        return
    if asked in ("kv_pages", "speculative"):
        pipe = decode.DecodePipeline(entry.family.FAMILY, entry.config,
                                     [(1, 24)], [params], max_len=32)
    if asked == "kv_pages":
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import serve
        with pytest.raises(NotImplementedError, match="laguna"):
            serve._Service(pipe, kv_pages=4)
        return
    if asked == "speculative":
        from pipeedge_tpu.parallel.speculative import SpeculativeDecoder
        draft = decode.build_decode_pipeline("pipeedge/test-tiny-gpt2", None,
                                             max_len=32)
        for target, drafter in ((pipe, draft), (draft, pipe)):
            with pytest.raises(NotImplementedError,
                               match="laguna.*k_ring.*rings"):
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
        decode.DecodePipeline(entry.family.FAMILY, entry.config, [(1, 24)],
                              [params], max_len=32, **option)


def test_the_cells_cut_is_a_decoder_the_clis_take():
    assert registry.decoder_model(CELL) == CELL
    assert registry.decoder_model(WHOLE) == WHOLE
    entry = registry.get_model_entry(CELL)
    cfg = entry.config
    assert (entry.layers, cfg.num_hidden_layers, cfg.held_experts,
            cfg.n_experts, cfg.vocab_size, cfg.sliding_window) \
        == (20, 5, (), 256, 100352, 512)
    assert 7680 % cfg.prefill_chunk == 0 \
        and cfg.sliding_window % cfg.prefill_chunk == 0
    # every parameter of the cut, by the loader's shapes
    stage = ShardConfig(1, 20, is_first=True, is_last=True)
    params = jax.eval_shape(lambda: laguna._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert count == costs.held_parameters(_config(tiny=False)) == 3869867264


# -- the benchmark's cost functions --------------------------------------------

def test_costs_against_hand_counts():
    config = _config(tiny=False)
    d, hd = 2048, 128
    assert costs.attention_params(config, 48) \
        == 2 * d * 48 * hd + 48 * d + 2 * d * 8 * hd + 2 * hd == 29458688
    assert costs.attention_params(config, 64) == 37880064
    assert costs.dense_ffn_params(config) == 3 * d * 8192
    assert costs.expert_params(config) == 3 * d * 512 == 3145728
    assert costs.shared_params(config) == 3 * d * 512 + d
    assert costs.kv_bytes_a_token(config) == 2 * 2 * 8 * hd * 4 == 16384
    assert costs.ring_bytes_a_row(config) == 3 * 512 * 8192
    assert costs.ring_bytes_a_row(config, 100) == 3 * 100 * 8192
    # a window of 512: query t attends min(t + 1, 512) positions
    for first, count in ((0, 1), (0, 512), (0, 7680), (500, 128), (7680, 1)):
        assert costs.window_pairs(config, first, count) == sum(
            min(t + 1, 512) for t in range(first, first + count))
    # a step at 7,936 positions, 32 rows, 163 experts touched a layer:
    # ISSUE 40's 9.5 GB
    step = costs.decode_step_bytes(config, 32, 7936, 163)
    assert 9.4e9 < step < 9.7e9
    assert costs.decode_step_bytes(config, 32, 8000, 163) - step \
        == 32 * 64 * 16384      # the rings do not grow
    # a prompt's attention: 46 TFLOP in the two full layers, 12 in the
    # three window layers
    prompt = costs.attention_flops(config, 0, 7680) * 32
    full = 32 * 2 * 4 * 48 * hd * 7680 * 7681 // 2
    assert prompt - full == 32 * 3 * 4 * 64 * hd \
        * costs.window_pairs(config, 0, 7680)
    assert 46e12 < full < 47e12 and 11.9e12 < prompt - full < 12e12


def test_rows_step_together_through_the_block_it_shares_with_mellum(tiny):
    """The served executor's step of rows that stand each at its own
    position (parallel/decode_rows.py) over laguna's own block (a gate a
    head, a shared expert, a leading dense layer, two head counts, three
    kinds of run in one stage): the code `tests/test_mellum.py` holds to the
    reference under the other family's name gives each request, token for
    token, what it gets alone."""
    from pipeedge_tpu.parallel import decode_rows
    from pipeedge_tpu.parallel.batcher import ContinuousBatcher
    config, _, pipe, _, _ = tiny
    assert decode_rows.rows_block_fn(pipe) is laguna.rows_block_step
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, config["vocab_size"], size=(1, n))
               for n in (4, 11, 26)]
    batcher = ContinuousBatcher(pipe, max_active=4)
    assert batcher.rows is not None
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=10)
    results = batcher.run()
    for i, ids in enumerate(prompts):
        np.testing.assert_array_equal(
            results[i], np.asarray(pipe.generate(ids, 10)))
