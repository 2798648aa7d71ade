"""The brumby family (models/brumby.py: Qwen3's trunk with every attention a
power-retention layer; a cache with no position axis at all) against the
benchmark's plain reference (benchmark/reference/brumby.py: the attention
form, which shares no code with it and never forms `phi`), on the tiny
twin: four blocks, two KV heads of two query heads of 8, chunks of 4 in
spans of 6 (a span's last chunk is short)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_brumby as costs, prom as bench_prom, weights
from benchmark.reference import brumby as reference
from benchmark.schemes import brumby as scheme
from pipeedge_tpu.models import ShardConfig, brumby, registry, stage_cache
from pipeedge_tpu.models.shard import shard_apply
from pipeedge_tpu.ops import retention_step as retention_kernel
from pipeedge_tpu.parallel import decode
from pipeedge_tpu.telemetry import metrics as prom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "pipeedge/test-tiny-brumby"
WHOLE = "manifestai/Brumby-14B-Base"
CELL = WHOLE + "@10"
LENGTH = 30


def _config(tiny=True, **over):
    name = "brumby-14b-base.json"
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


def _gap(got, wanted):
    return float(np.abs(got - wanted).max() / (wanted.max() - wanted.min()))


def _louder(path):
    """The seeded weights with every projection times 8 (exact in float16
    and in bfloat16): at the tiny widths the pool's 0.02 leaves every gate
    at a half and every SiLU in its linear part, which would hide a decay
    or a SwiGLU computed wrongly."""
    with np.load(path) as file:
        held = {key: file[key] for key in file.files}
    for key in held:
        if key.endswith("proj.weight"):
            held[key] = held[key] * np.float16(8.0)
    np.savez(path, **held)
    return path


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The benchmark's tiny cut in one stage, its projections louder
    (`_louder`): (config, weights file, pipeline, ids [2, 30], reference
    logits)."""
    config = _config()
    path = _louder(weights.write(config, 2 ** 31 + 7, str(
        tmp_path_factory.mktemp("brumby") / "weights.npz")))
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.float32,
        model_file=path)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)
    return config, path, pipe, ids, wanted


# float32 program against float32 reference: they differ by the order of
# their sums (the chunked and the recurrent form over an expanded state
# against the attention form, the three-part products against one float32
# product) and the output is a RATIO of two such sums: 2.2e-6 of the logits'
# range measured at the worst of the cases below, 5e-7 at most of them. The
# model makes no discrete choice, so nothing amplifies a rounding further:
# 1e-5 leaves room for another BLAS and fails a bfloat16 product or state
# (1e-3) by a hundred times
TOLERANCE = 1e-5


# -- the mixer's three forms -----------------------------------------------------

@pytest.mark.parametrize("hd", [8, 16, 128])
def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k(hd):
    rng = np.random.default_rng(hd)
    q, k = (jnp.asarray(rng.normal(size=(5, hd)), jnp.float32)
            for _ in range(2))
    expanded = brumby.phi(q)
    assert expanded.shape == (5, brumby.expanded(hd))
    assert brumby.expanded(hd) == (hd // 2 + 1) * hd >= hd * (hd + 1) // 2
    wanted = np.sum(np.asarray(q, np.float64) * np.asarray(k, np.float64),
                    -1) ** 2
    np.testing.assert_allclose(
        np.sum(np.asarray(expanded, np.float64)
               * np.asarray(brumby.phi(k), np.float64), -1), wanted,
        rtol=1e-5, atol=1e-5)
    if hd == 128:   # 65 whole rows of lanes: 0.8% over the distinct products
        assert brumby.expanded(hd) == 8320 and hd * (hd + 1) // 2 == 8256


def _draws(seed, rows, length, groups, per_group, hd):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return rng.normal(size=shape)

    q = draw(rows, length, groups, per_group, hd)
    k, v = draw(rows, length, groups, hd), draw(rows, length, groups, hd)
    a = np.log(1.0 / (1.0 + np.exp(-2.0 * draw(rows, length, groups))))
    return q, k, v, a


def _attention_form(q, k, v, a):
    """The module docstring's attention form in float64: num [B, S, G, R,
    hd] and den [B, S, G, R] (q and k already scaled)."""
    length = a.shape[1]
    run = np.cumsum(a, axis=1)
    live = np.arange(length)[:, None] >= np.arange(length)[None, :]
    decay = np.where(live[None, :, :, None], np.exp(np.where(
        live[None, :, :, None], run[:, :, None] - run[:, None, :], 0.0)),
        0.0)                                                # [B, t, j, G]
    w = np.einsum("btgrd,bjgd->btjgr", q, k) ** 2 * decay[..., None]
    return np.einsum("btjgr,bjgd->btgrd", w, v), w.sum(axis=2)


def _f32(*arrays):
    return tuple(jnp.asarray(x, jnp.float32) for x in arrays)


def test_the_recurrence_is_the_attention_form():
    rows, length, groups, per_group, hd = 2, 9, 2, 3, 8
    q, k, v, a = _draws(1, rows, length, groups, per_group, hd)
    wanted_num, wanted_den = _attention_form(q, k, v, a)
    width = brumby.expanded(hd)
    state = jnp.zeros((rows, groups, hd, width))
    zsum = jnp.zeros((rows, groups, width))
    qf, kf, vf, af = _f32(q, k, v, a)
    for t in range(length):
        num, den, state, zsum = brumby.retention_step(
            qf[:, t], kf[:, t], vf[:, t], af[:, t], state, zsum)
        np.testing.assert_allclose(num, wanted_num[:, t], rtol=2e-4,
                                   atol=2e-4)
        np.testing.assert_allclose(den, wanted_den[:, t], rtol=2e-4,
                                   atol=2e-4)


@pytest.mark.parametrize("spans", [(7,), (6, 6, 3), (4, 1, 5, 2)])
def test_the_chunked_form_is_the_stepped_one_across_spans(spans):
    """Chunks of 4: a span's last chunk is short (7 = 4 + 3, 6 = 4 + 2), a
    span may be shorter than a chunk, and the state and the sum of keys are
    carried from a span to the next."""
    rows, groups, per_group, hd = 2, 2, 2, 8
    length = sum(spans)
    q, k, v, a = _f32(*_draws(2, rows, length, groups, per_group, hd))
    width = brumby.expanded(hd)
    zeros = (jnp.zeros((rows, groups, hd, width)),
             jnp.zeros((rows, groups, width)))
    state, zsum = zeros
    stepped = []
    for t in range(length):
        num, den, state, zsum = brumby.retention_step(
            q[:, t], k[:, t], v[:, t], a[:, t], state, zsum)
        stepped.append((num, den))
    got, (c_state, c_zsum), start = [], zeros, 0
    for span in spans:
        at = slice(start, start + span)
        num, den, c_state, c_zsum = brumby.retention_chunked(
            q[:, at], k[:, at], v[:, at], a[:, at], c_state, c_zsum,
            min(4, span))
        got += [(num[:, i], den[:, i]) for i in range(span)]
        start += span
    for (num, den), (wanted_num, wanted_den) in zip(got, stepped):
        np.testing.assert_allclose(num, wanted_num, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(den, wanted_den, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(c_state, state, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(c_zsum, zsum, rtol=2e-5, atol=2e-5)


# -- against the reference -------------------------------------------------------

# the tiny model prefills in spans of 6 and chunks of 4: within a span (3), a
# span (6), across a span boundary (13), three spans (18), and a last span
# shorter than a chunk (20)
@pytest.mark.parametrize("prompt_len", [3, 6, 13, 18, 20])
def test_spans_then_decode_match_the_reference(prompt_len, tiny):
    _, _, pipe, ids, wanted = tiny
    got = _logits_through_the_cache(pipe, ids, prompt_len)
    assert _gap(got, wanted[:, prompt_len - 1:]) < TOLERANCE


def test_a_whole_prompt_prefill_is_the_spans(tiny):
    """The served path's prefill program (the whole prompt in one call, the
    state from zeros and not from the cache) leaves what the spans leave."""
    _, _, pipe, ids, wanted = tiny
    stage = pipe.stages[0]
    data, cache = stage["prefill"](stage["params"],
                                   jnp.asarray(ids[:, :20], jnp.int32),
                                   pipe._fresh_caches(2)[0])
    _, spans = pipe._prefill(jnp.asarray(ids[:, :20], jnp.int32))
    assert _gap(np.asarray(data[:, -1]), wanted[:, 19]) < TOLERANCE
    for name in ("pr_state", "pr_sum"):
        np.testing.assert_allclose(cache[name], spans[0][name], rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("left_out", ["gate", "rotation"])
def test_each_equation_left_out_fails_the_comparison(left_out, tiny,
                                                     monkeypatch):
    """The comparison sees the assumed equations: the program without the
    decay (a plain second-power attention) or without the rotation is off
    by a hundred tolerances and more."""
    config, path, _, ids, wanted = tiny
    if left_out == "gate":
        monkeypatch.setattr(jax.nn, "log_sigmoid",
                            lambda x: jnp.zeros_like(x))
    else:
        monkeypatch.setattr(brumby, "rotate_halves",
                            lambda x, pos, freqs: x)
    pipe = decode.build_decode_pipeline(TINY, None, max_len=32,
                                        dtype=jnp.float32, model_file=path)
    assert _gap(_logits_through_the_cache(pipe, ids, 13),
                wanted[:, 12:]) > 100 * TOLERANCE


def test_the_reference_refuses_what_the_program_has_not():
    config = _config()
    for key, value in (("sliding_window", 8), ("use_sliding_window", True),
                       ("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            reference.forward(dict(config, **{key: value}), {},
                              np.zeros((1, 4), np.int64))


# -- the state kernel ------------------------------------------------------------

@pytest.mark.parametrize("hd, per_group, layer", [(8, 2, 0), (16, 5, 2),
                                                  (32, 5, 1)])
def test_the_state_kernel_is_the_jnp_step_in_place(hd, per_group, layer):
    """`ops/retention_step.py` in interpret mode against
    `brumby.retention_step`, the other layers of the stack untouched; five
    query heads a KV head as published (the published 128 lanes and 65
    diagonals compile for the described chip in
    `test_chip_compile_families.py` and ran on the chip: PERF.md, PR 58)."""
    rng = np.random.default_rng(hd + layer)
    rows, groups, width = 2, 2, brumby.expanded(hd)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    stack = draw(3, rows, groups, hd, width)
    zsum = draw(rows, groups, width)
    q, k, v = draw(rows, groups, per_group, hd), draw(rows, groups, hd), \
        draw(rows, groups, hd)
    a = jax.nn.log_sigmoid(draw(rows, groups))
    wanted_num, wanted_den, wanted, wanted_sum = brumby.retention_step(
        q, k, v, a, stack[layer], zsum)
    got, got_sum, num, den = retention_kernel.step(
        stack, jnp.int32(layer), brumby.exp_ulp(a), zsum, q, k, v,
        interpret=True)
    scale = float(jnp.abs(wanted_num).max())
    np.testing.assert_allclose(num, wanted_num, rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(den, wanted_den, rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(got[layer], wanted, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_sum, wanted_sum, rtol=1e-6, atol=1e-6)
    for other in set(range(3)) - {layer}:
        np.testing.assert_array_equal(got[other], stack[other])
    assert retention_kernel.whole_tiles(128)
    assert not retention_kernel.whole_tiles(8)


@pytest.mark.parametrize("placed", [False, True], ids=["gathered", "placed"])
def test_a_steps_state_through_the_kernel_is_the_jnp_steps(placed,
                                                           monkeypatch):
    """`brumby.state_kernel_mode` in the family's stage: a prefill and four
    steps with the state kernel (interpret mode) against the same with the
    jnp step, the four blocks each handing the stack on;
    `retention_steps_in_place` counts every stepped position where the
    driver places the leaf and none where it does not."""
    if placed:
        monkeypatch.setattr(decode, "WHOLE_IN_PLACE_BYTES", 1 << 11)
    rows, prompt, steps, layers = 2, 11, 4, 4
    ids = np.random.default_rng(11).integers(0, 100,
                                             size=(rows, prompt + steps))
    in_place, stepped = (brumby.STATS.index(name) for name in (
        "retention_steps_in_place", "retention_positions_stepped"))
    out = []
    for mode in (None, "interpret"):
        monkeypatch.setattr(brumby, "_kernel_mode", lambda mode=mode: mode)
        pipe = decode.build_decode_pipeline(TINY, None, max_len=32)
        data, caches = pipe._prefill(jnp.asarray(ids[:, :prompt], jnp.int32))
        logits = [np.asarray(data[:, -1])]
        after_prefill = stage_cache.read_stats(caches[0])
        for pos in range(prompt, prompt + steps):
            data, caches = pipe.extend(ids[:, pos:pos + 1], caches, pos)
            logits.append(np.asarray(data[:, 0]))
        counts = stage_cache.read_stats(caches[0]) - after_prefill
        assert counts[stepped] == rows * steps * layers
        assert counts[in_place] == (
            rows * steps * layers if placed and mode else 0)
        assert after_prefill[in_place] == 0
        out.append((np.stack(logits, 1), {
            name: np.asarray(caches[0][name])
            for name in ("pr_state", "pr_sum")}))
    (wanted, cache), (got, kernel_cache) = out
    assert _gap(got, wanted) < 1e-6
    for name, leaf in cache.items():
        assert leaf.shape == kernel_cache[name].shape
        assert _gap(kernel_cache[name], leaf) < 1e-6, name


# -- stages, programs, the cache -------------------------------------------------

@pytest.mark.parametrize("cut", [8])
def test_two_stages_cut_at_a_block_boundary_give_the_one_stage_logits(
        cut, tiny):
    config, path, pipe, ids, wanted = tiny
    two = decode.build_decode_pipeline(
        TINY, [(1, cut), (cut + 1, 16)], max_len=32, dtype=jnp.float32,
        model_file=path)
    assert [c["pr_state"].shape[0] for c in two._fresh_caches(2)] \
        == [cut // 4, 4 - cut // 4]
    got = _logits_through_the_cache(two, ids, 13)
    assert _gap(got, wanted[:, 12:]) < TOLERANCE
    np.testing.assert_allclose(got, _logits_through_the_cache(pipe, ids, 13),
                               atol=1e-6)


def _builds():
    return {(labels["program"], labels["step"]): value
            for labels, value in bench_prom.samples(
                prom.REGISTRY.render(), "pipeedge_jax_program_builds_total")}


def _attended():
    return sum(value for _, value in bench_prom.samples(
        prom.REGISTRY.render(), "pipeedge_attend_positions_total"))


def test_a_generation_builds_one_span_and_one_step_program():
    """No leaf is a row a position, so the stage binds ONE attend width: a
    prompt of ten spans and 40 steps, which cross five widths of the
    ladder's fine octaves at `max_len` 128, trace two `decode_step`
    programs; other positions and another count of tokens trace none, and
    no attended position is counted."""
    pipe = decode.build_decode_pipeline(TINY, None, max_len=64)
    assert not pipe.keeps_positions
    assert {pipe._read_len(pos, span, octave) for pos in (0, 7, 40, 63)
            for span, octave in ((1, 1), (1, 4), (6, 4))
            if pos + span <= 64} == {64}
    ids = np.random.default_rng(5).integers(0, 100, size=(2, 60))
    before, attended = _builds(), _attended()
    pipe.generate(ids[:, :24], 40)
    gained = {key: value - before.get(key, 0.0)
              for key, value in _builds().items()}
    assert gained["decode_step", "trace"] == 2
    assert gained["decode_step", "lower"] == 2
    again = _builds()
    pipe.generate(ids[:, 6:18], 9)
    pipe.generate(ids[:, :54], 3)
    assert {key: value for key, value in _builds().items()
            if key[0] == "decode_step"} \
        == {key: value for key, value in again.items()
            if key[0] == "decode_step"}
    assert _attended() == attended
    # a sibling with rows a position keeps its ladder
    for name in ("pipeedge/test-tiny-gpt2",
                 "pipeedge/test-tiny-granite-hybrid"):
        sibling = decode.build_decode_pipeline(name, None, max_len=64,
                                               attend_floor=16)
        assert sibling.keeps_positions
        assert (sibling._read_len(3), sibling._read_len(40)) == (16, 64)


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_a_fresh_cache_has_no_position_axis(size):
    """Both leaves `[L, B, ...]`, whatever `max_len`; at the cell's sizes
    2.75 GB of state and sums at 8 rows of ten layers."""
    entry = registry.get_model_entry(TINY if size == "tiny" else CELL)
    cfg, leaves = entry.config, entry.family.cache_leaves(entry.config)
    rows = 8
    shapes = {max_len: jax.eval_shape(lambda max_len=max_len:
                                      stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len, leaves=leaves))
        for max_len in (64, 2048)}
    groups, hd = cfg.kv_heads, cfg.head_dim
    width = brumby.expanded(hd)
    for cache in shapes.values():
        assert cache["pr_state"].shape == (cfg.num_hidden_layers, rows,
                                           groups, hd, width)
        assert cache["pr_sum"].shape == (cfg.num_hidden_layers, rows, groups,
                                         width)
    assert stage_cache.whole_names(leaves) == ("pr_state", "pr_sum")
    if size == "published":
        held = sum(leaf.size * 4 for name, leaf in shapes[2048].items()
                   if name != "stats")
        assert held == rows * costs.state_bytes_a_row(_config(tiny=False)) \
            == 2747596800
        # the state is placed (a layer's rows are 273 MB), the sums gathered
        assert rows * groups * hd * width * 4 >= decode.WHOLE_IN_PLACE_BYTES
        assert 10 * rows * groups * width * 4 < decode.WHOLE_IN_PLACE_BYTES


def _counters():
    return {(name, phase): prom.REGISTRY.counter(
        f"pipeedge_{name}_total", "").value(phase=phase)
        for name in brumby.STATS for phase in ("prefill", "decode")}


def test_counters_of_one_batch_are_what_its_sizes_predict(tiny):
    _, _, pipe, ids, _ = tiny
    before = _counters()
    pipe.generate(ids[:, :20], 8)
    gained = {key: value - before[key] for key, value in _counters().items()}
    # 2 rows x 20 positions x 4 layers, in spans of 6, 6, 6 and 2
    assert gained["retention_positions_chunked", "prefill"] == 2 * 20 * 4
    assert gained["retention_positions_stepped", "prefill"] == 0
    assert gained["retention_positions_chunked", "decode"] == 0
    assert gained["retention_positions_stepped", "decode"] == 2 * 7 * 4
    # the CPU keeps the jnp step
    assert gained["retention_steps_in_place", "decode"] == 0
    gauge = prom.REGISTRY.gauge("pipeedge_cache_leaf_bytes", "")
    assert gauge.value(leaf="pr_state") == 4 * 2 * 2 * 8 * 40 * 4
    assert gauge.value(leaf="pr_sum") == 4 * 2 * 2 * 40 * 4


def test_a_prefix_is_a_state_broadcast_over_the_batch(tiny):
    _, _, pipe, ids, _ = tiny
    whole = np.asarray(pipe.generate(ids[:1, :20], 6))
    handle = pipe.precompute_prefix(ids[0, :12])
    suffix = np.repeat(ids[:1, 12:20], 3, axis=0)
    got = np.asarray(pipe.generate(suffix, 6, prefix=handle))
    for row in got:
        np.testing.assert_array_equal(row[8:], whole[0, 20:])


# -- loading ---------------------------------------------------------------------

def test_the_loader_reads_the_schemes_keys(tiny):
    """Every key the scheme writes is read by the loader, each once, at the
    scheme's shape; the gate's projection stays float32."""
    config, path, pipe, _, _ = tiny
    cfg = registry.get_model_config(TINY)
    asked = {}

    def get(key, shape):
        assert key not in asked
        asked[key] = shape
        return jnp.zeros(shape)

    stage = ShardConfig(1, 16, is_first=True, is_last=True)
    jax.eval_shape(lambda: brumby._assemble(cfg, stage, get, jnp.bfloat16))
    written = scheme.tensors(config, lambda shape, mean=0.0: np.zeros(
        shape, np.float16))
    assert {key: tuple(value.shape) for key, value in written.items()} \
        == asked
    assert "model.layers.3.self_attn.g_proj.weight" in asked
    params = registry.module_shard_factory(TINY, path, 1, 16,
                                           dtype=jnp.bfloat16,
                                           unroll=False)[1]
    assert params["blocks"]["gate"]["w"].dtype == jnp.float32
    assert params["blocks"]["q"]["w"].dtype == jnp.bfloat16
    assert params["blocks"]["mlp"]["gate"].dtype == jnp.bfloat16


def test_the_cells_model_is_a_decoder_the_clis_take():
    assert registry.decoder_model(CELL) == CELL
    entry, whole = registry.get_model_entry(CELL), \
        registry.get_model_entry(WHOLE)
    cfg = entry.config
    assert (whole.layers, entry.layers, cfg.num_hidden_layers,
            cfg.vocab_size) == (160, 40, 10, 151936)
    # the cell's prompt in whole spans of whole chunks; a span of 8 rows
    # needs no chunks of rows under the driver's 512 MiB
    assert 1024 % cfg.prefill_chunk == 0 \
        and cfg.prefill_chunk % cfg.linear_chunk == 0
    assert 8 * cfg.prefill_chunk * cfg.intermediate_size * 12 <= 1 << 29
    # every parameter held, by the loader's shapes
    stage = ShardConfig(1, 40, is_first=True, is_last=True)
    params = jax.eval_shape(lambda: brumby._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert count == costs.held_parameters(_config(tiny=False)) == 4859358720
    assert costs.held_parameters(_config(
        tiny=False, num_hidden_layers=40)) == 14769945600
    with pytest.raises(ValueError, match="no cut"):
        registry.get_model_entry(WHOLE + "@41")
    with pytest.raises(ValueError, match="no cut"):
        registry.get_model_entry(WHOLE + "@4,e0+2")


@pytest.mark.parametrize("tiny_cut", [False, True])
def test_the_registry_holds_the_configurations_sizes(tiny_cut):
    config = _config(tiny=tiny_cut)
    cfg = registry.get_model_config(config["program_model"])
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.kv_heads, cfg.head_dim, cfg.vocab_size,
            cfg.intermediate_size, cfg.layer_norm_eps, cfg.rope_theta,
            cfg.max_position_embeddings) == (
                config["hidden_size"], config["num_hidden_layers"],
                config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"], config["vocab_size"],
                config["intermediate_size"], config["rms_norm_eps"],
                config["rope_theta"], config["max_position_embeddings"])
    assert config["tie_word_embeddings"] is False
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] \
        == (4 if tiny_cut else 40)


def test_costs_of_a_step_are_the_weights_and_the_state_twice():
    config = _config(tiny=False)
    step = costs.decode_step_bytes(config, 8)
    state = 2 * 8 * costs.state_bytes_a_row(config)
    assert abs(step - 13.66e9) < 0.01e9 and abs(state / step - 0.40) < 0.01
    assert costs.weight_bytes(config) == 2 * (4859358720 - 5120 * 151936)
    # a token a layer: 0.66 GFLOP of weights, 0.10 of the state's products
    assert abs(costs.token_product_flops(config) / 10 - 0.6606e9) < 1e6
    chunk = costs.chunk_flops(config) / 128
    assert 0.10e9 < chunk < 0.115e9
    assert costs.prefill_flops(config, 8, 1024) > 8 * 1024 * (
        costs.token_product_flops(config))


# -- what it runs, and what it refuses by name ------------------------------------

def test_tools_generate_takes_the_model_and_its_cut(capsys, monkeypatch):
    import sys
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import generate
    from pipeedge_tpu import utils
    monkeypatch.setattr(utils, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "generate.py", "-m", TINY + "@3,v60", "-b", "2", "--prompt-len",
        "12", "--new-tokens", "4", "--max-len", "32"])
    generate.main()
    assert "tokens" in capsys.readouterr().out


@pytest.mark.parametrize("asked", ["mesh", "sp_mesh", "ep_mesh",
                                   "tp_ep_mesh", "cache_bits", "forward",
                                   "kv_pages", "speculative", "spmd",
                                   "odd_head"])
def test_what_the_family_cannot_do_is_refused_by_name(asked):
    from jax.sharding import Mesh
    entry = registry.get_model_entry(TINY)
    _, params, stage = registry.module_shard_factory(TINY, None, 1, 16,
                                                     unroll=False)
    if asked == "forward":
        with pytest.raises(NotImplementedError, match="brumby"):
            shard_apply(entry.family.FAMILY, entry.config, stage, params,
                        jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(NotImplementedError, match="brumby"):
            brumby.FAMILY.sublayer({}, 0, None, entry.config)
        with pytest.raises(NotImplementedError, match="brumby"):
            brumby.init_params(entry.config, ShardConfig(1, 2))
        return
    if asked == "odd_head":
        import dataclasses
        with pytest.raises(ValueError, match="diagonals"):
            brumby.cache_leaves(dataclasses.replace(entry.config,
                                                    attn_head_dim=7))
        return
    if asked == "spmd":
        from pipeedge_tpu.parallel.spmd_decode import SpmdDecodePipeline
        mesh = Mesh(np.array(jax.devices()[:1]), ("stage",))
        with pytest.raises(NotImplementedError, match="brumby"):
            SpmdDecodePipeline(entry.family.FAMILY, entry.config, [(1, 16)],
                               [params], mesh, max_len=32)
        return
    if asked in ("kv_pages", "speculative"):
        pipe = decode.DecodePipeline(entry.family.FAMILY, entry.config,
                                     [(1, 16)], [params], max_len=32)
    if asked == "kv_pages":
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import serve
        with pytest.raises(NotImplementedError, match="brumby"):
            serve._Service(pipe, kv_pages=4)
        return
    if asked == "speculative":
        from pipeedge_tpu.parallel.speculative import SpeculativeDecoder
        draft = decode.build_decode_pipeline("pipeedge/test-tiny-gpt2", None,
                                             max_len=32)
        for target, drafter in ((pipe, draft), (draft, pipe)):
            with pytest.raises(NotImplementedError,
                               match="brumby.*earlier position"):
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
        decode.DecodePipeline(entry.family.FAMILY, entry.config, [(1, 16)],
                              [params], max_len=32, **option)
