"""The granite_hybrid family (models/granite_hybrid.py: blocks of a mixer
THEN a SwiGLU, Mamba-2 with ONE group of heads or attention without
positions, the Granite line's four constants, a tied head) against the
benchmark's plain reference (benchmark/reference/granite_hybrid.py), which
shares no code with it, on the tiny twin: eight blocks `MM*MMM*M`, four
heads in one group, the attention not first in the stage."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_granite_hybrid as costs, weights
from benchmark.reference import granite_hybrid as reference
from pipeedge_tpu.models import (ShardConfig, granite_hybrid, mamba2,
                                 registry, stage_cache)
from pipeedge_tpu.models.shard import BlockRuns, kind_runs, shard_apply
from pipeedge_tpu.ops import ssm_step as ssm_kernel
from pipeedge_tpu.parallel import decode
from pipeedge_tpu.telemetry import metrics as prom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "pipeedge/test-tiny-granite-hybrid"
WHOLE = "ibm-granite/granite-4.0-h-micro"
LENGTH = 30


def _config(tiny=True, **over):
    name = "granite-4.0-h-micro.json"
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
    and in bfloat16). At the tiny widths the pool's 0.02 leaves every score
    near 0 and every SiLU in its linear part: a softmax that is uniform
    whatever scales or turns its scores, and a SwiGLU that is the same
    with gate and up swapped, would hide the equations held below."""
    with np.load(path) as file:
        held = {key: file[key] for key in file.files}
    for key in held:
        if key.endswith(("proj.weight", "linear.weight")):
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
        tmp_path_factory.mktemp("granite_hybrid") / "weights.npz")))
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.float32,
        model_file=path)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)
    return config, path, pipe, ids, wanted


# float32 program against float32 reference: they differ by the order of
# their sums (the chunked form against the recurrence a position, the
# three-part products against one float32 product; 5e-8 of the logits' range
# measured). The model makes no discrete choice, so nothing amplifies a
# rounding: 1e-6 leaves room for another BLAS and fails a bfloat16 product
# or state (1e-3), and each equation left out below by 100 times and more
TOLERANCE = 1e-6


# the tiny model prefills in spans of 8 and chunks of 4: within a span and
# not a multiple of the chunk (3), a span (8), across a span boundary and not
# a multiple of the chunk (13, 21), two spans (16)
@pytest.mark.parametrize("prompt_len", [3, 8, 13, 16, 21])
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
                                   jnp.asarray(ids[:, :21], jnp.int32),
                                   pipe._fresh_caches(2)[0])
    _, spans = pipe._prefill(jnp.asarray(ids[:, :21], jnp.int32))
    assert _gap(np.asarray(data[:, -1]), wanted[:, 20]) < TOLERANCE
    for name in ("k", "v", "ssm_state", "ssm_conv"):
        np.testing.assert_allclose(cache[name], spans[0][name], rtol=1e-5,
                                   atol=1e-5)


def test_bfloat16_weights_are_computed_on_in_float32(tiny):
    """The cell's precision: weights as stored, activations, state and cache
    float32. The scheme's values are bfloat16's, so nothing is rounded."""
    config, path, _, ids, wanted = tiny
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.bfloat16,
        model_file=path)
    kept = {leaf.dtype for leaf in jax.tree_util.tree_leaves(
        pipe.stages[0]["params"])}
    assert kept == {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}
    cache = pipe._fresh_caches(2)[0]
    assert cache["ssm_state"].dtype == cache["k"].dtype == jnp.float32
    got = _logits_through_the_cache(pipe, ids, 13)
    assert _gap(got, wanted[:, 12:]) < TOLERANCE


# -- each assumed equation shows when left out ---------------------------------

@pytest.mark.parametrize("key, default", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 8 ** -0.5), ("logits_scaling", 1.0)])
def test_each_multiplier_left_at_its_default_fails_the_comparison(
        key, default, tiny):
    """The Granite line's four constants: the reference with one at what a
    plain decoder has (1, and `head_dim**-0.5` for the scores) is no longer
    the program's."""
    config, path, pipe, ids, wanted = tiny
    got = _logits_through_the_cache(pipe, ids, 13)
    with np.load(path) as tensors:
        without = reference.forward(dict(config, **{key: default}), tensors,
                                    ids)[:, 12:]
    assert _gap(got, wanted[:, 12:]) < TOLERANCE
    assert _gap(got, without) > 100 * TOLERANCE


def _edited(path, tmp_path, **tensors):
    """The tiny weights with some tensors replaced."""
    with np.load(path) as file:
        held = {key: file[key] for key in file.files}
    for suffix, value in tensors.items():
        for key in held:
            if key.endswith(suffix):
                held[key] = np.full_like(held[key], value)
    out = str(tmp_path / "edited.npz")
    np.savez(out, **held)
    return out


@pytest.mark.parametrize("suffix, value", [
    ("mamba.D", 0.0), ("mamba.dt_bias", 0.0), ("conv1d.bias", 0.0)])
def test_each_tensor_left_out_fails_the_comparison(suffix, value, tiny,
                                                   tmp_path):
    config, path, pipe, ids, wanted = tiny
    got = _logits_through_the_cache(pipe, ids, 13)
    with np.load(_edited(path, tmp_path, **{suffix: value})) as tensors:
        without = reference.forward(config, tensors, ids)[:, 12:]
    assert _gap(got, without) > 100 * TOLERANCE


@pytest.mark.parametrize("left_out", ["rotated", "gate_and_up_swapped",
                                      "gate_before_norm"])
def test_each_equation_left_out_fails_the_comparison(left_out, tiny,
                                                     monkeypatch):
    """An attention that rotates q and k at their positions, an
    `input_linear` read up-then-gate, and the Mamba-2 gate after the norm:
    the reference with one changed is no longer the program's."""
    config, path, pipe, ids, wanted = tiny
    got = _logits_through_the_cache(pipe, ids, 13)
    # each stands in for one of the programs `forward` compiles (a new
    # function is a new trace; the reference's own stay cached as they are)
    if left_out == "rotated":
        def rotated(x, w, eps, heads, groups, scale, residual, block):
            q, k, v = reference._attention_inputs(x, w, eps, heads, groups)
            half = q.shape[-1] // 2
            angles = jnp.arange(x.shape[0])[:, None] \
                * 10000.0 ** (-jnp.arange(half) / half)
            cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]

            def turn(t):
                a, b = t[..., :half], t[..., half:]
                return jnp.concatenate([a * cos - b * sin,
                                        b * cos + a * sin], -1)
            mixed = jnp.concatenate([reference._attention_block(
                turn(q)[start:start + block], turn(k), v, start, scale)
                for start in range(0, x.shape[0], block)])
            return x + residual * (mixed @ w["o"].T)
        monkeypatch.setattr(reference, "_attention_layer", rotated)
    elif left_out == "gate_and_up_swapped":
        plain_part = reference._swiglu_part

        def swapped(acc, x, ln, gate_w, up_w, down_w, eps, residual):
            return plain_part(acc, x, ln, up_w, gate_w, down_w, eps, residual)
        monkeypatch.setattr(reference, "_swiglu_part", swapped)
    else:                               # the norm first, then the gate

        def gate_after(x, xs, b, c, dt, decay, z, d_skip, norm_w, out_proj,
                       eps, groups, residual):
            length = x.shape[0]
            y = reference._recurrence(
                xs.reshape(length, decay.shape[1], -1), b, c, dt, decay,
                d_skip).reshape(length, groups, -1)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
            y = y.reshape(length, -1) * norm_w * jax.nn.silu(z)
            return x + residual * (y @ out_proj.T)
        monkeypatch.setattr(reference, "_mamba_rest", gate_after)
    with np.load(path) as tensors:
        without = reference.forward(config, tensors, ids)[:, 12:]
    assert _gap(got, wanted[:, 12:]) < TOLERANCE
    assert _gap(got, without) > 100 * TOLERANCE


def test_the_reference_refuses_what_the_program_has_not():
    config = _config()
    ids = np.zeros((1, 4), np.int64)
    with pytest.raises(ValueError, match="position_embedding_type"):
        reference.forward(dict(config, position_embedding_type="rope"), {},
                          ids)
    with pytest.raises(ValueError, match="num_local_experts"):
        reference.forward(dict(config, num_local_experts=8), {}, ids)
    with pytest.raises(ValueError, match="routed experts"):
        weights.write(dict(config, num_local_experts=8), 1, "/nowhere/w.npz")


# -- the state kernel at ONE group of several heads ----------------------------

@pytest.mark.parametrize("heads, layer", [(4, 0), (4, 2), (16, 1)])
def test_the_state_kernel_at_one_group_is_the_jnp_step(heads, layer):
    """`ops/ssm_step.py` in interpret mode with every head on the one group
    (granite_hybrid's shape: a grid cell is a row's whole state) against
    `mamba2.ssm_step`, the other layers of the stack untouched."""
    rng = np.random.default_rng(heads + layer)
    rows, p, n = 3, 8, 16

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    stack = draw(3, rows, heads, p, n)
    x, bm, cm = draw(rows, 1, heads, p), draw(rows, 1, n), draw(rows, 1, n)
    dt = jax.nn.softplus(draw(rows, 1, heads) - 2.0)
    la = -dt * jnp.asarray(np.geomspace(1e-3, 16.0, heads), jnp.float32)
    wanted_y, wanted = mamba2.ssm_step(
        x, bm, cm, dt, la, stack[layer][:, None])
    got, y = ssm_kernel.step(
        stack, jnp.int32(layer), mamba2.exp_ulp(la).reshape(rows, heads),
        (dt[..., None] * x).reshape(rows, heads, p), bm, cm, interpret=True)
    np.testing.assert_allclose(y, wanted_y.reshape(rows, heads, p),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[layer], wanted[:, 0], rtol=1e-6,
                               atol=1e-6)
    for other in set(range(3)) - {layer}:
        np.testing.assert_array_equal(got[other], stack[other])
    # the cell's shape: one row's 2 MiB of state a grid cell, 64 cells
    assert ssm_kernel.row_tile(64, 64 * 64 * 128 * 4) == 1
    assert ssm_kernel.whole_tiles(64, 128)


@pytest.mark.parametrize("placed", [False, True], ids=["gathered", "placed"])
def test_a_steps_state_through_the_kernel_is_the_jnp_steps(placed,
                                                           monkeypatch):
    """`mamba2.state_kernel_mode` in this family's stage: a prefill and
    eight steps with the state kernel (interpret mode) against the same
    with the jnp step, the Mamba-2 runs of two, three and one blocks each
    handing the stack on; `ssm_steps_fused` counts every stepped position
    where the driver places the leaf and none where it does not."""
    if placed:
        monkeypatch.setattr(decode, "WHOLE_IN_PLACE_BYTES", 0)
    rows, prompt, steps, mamba_layers = 2, 11, 8, 6
    ids = np.random.default_rng(11).integers(0, 100,
                                             size=(rows, prompt + steps))
    fused, stepped = (granite_hybrid.STATS.index(name) for name in (
        "ssm_steps_fused", "ssm_positions_stepped"))
    out = []
    for mode in (None, "interpret"):
        monkeypatch.setattr(mamba2, "_kernel_mode", lambda mode=mode: mode)
        pipe = decode.build_decode_pipeline(TINY, None, max_len=32)
        data, caches = pipe._prefill(jnp.asarray(ids[:, :prompt], jnp.int32))
        logits = [np.asarray(data[:, -1])]
        after_prefill = stage_cache.read_stats(caches[0])
        for pos in range(prompt, prompt + steps):
            data, caches = pipe.extend(ids[:, pos:pos + 1], caches, pos)
            logits.append(np.asarray(data[:, 0]))
        counts = stage_cache.read_stats(caches[0]) - after_prefill
        assert counts[stepped] == rows * steps * mamba_layers
        assert counts[fused] == (
            rows * steps * mamba_layers if placed and mode else 0)
        assert after_prefill[fused] == 0
        out.append((np.stack(logits, 1), {
            name: np.asarray(caches[0][name])
            for name in ("ssm_state", "ssm_conv", "k", "v")}))
    (wanted, cache), (got, fused_cache) = out
    assert _gap(got, wanted) < 1e-6
    for name, leaf in cache.items():
        assert leaf.shape == fused_cache[name].shape
        assert _gap(fused_cache[name], leaf) < 1e-6, name


# -- stages, the tied head, the cache ------------------------------------------

@pytest.mark.parametrize("cut", [12, 20])
def test_two_stages_cut_at_a_block_boundary_give_the_one_stage_logits(
        cut, tiny):
    """After `MM*` and after `MM*MM` (inside a Mamba-2 run): the second
    stage holds the table as its head, the first as its embedding."""
    config, path, pipe, ids, wanted = tiny
    two = decode.build_decode_pipeline(
        TINY, [(1, cut), (cut + 1, 32)], max_len=32, dtype=jnp.float32,
        model_file=path)
    assert "head" not in two.stages[0]["params"].get("final", {})
    assert two.stages[1]["params"]["final"]["head"]["w"].shape == (100, 32)
    got = _logits_through_the_cache(two, ids, 13)
    assert _gap(got, wanted[:, 12:]) < TOLERANCE
    np.testing.assert_allclose(got, _logits_through_the_cache(pipe, ids, 13),
                               atol=1e-7)


def test_the_head_is_the_embeddings_array(tiny):
    """One table in the file and ONE array in the stage's parameters: the
    head's leaf is the embedding's, so the stage holds every parameter of
    the model once (and the trunk's two factors)."""
    config, path, pipe, _, _ = tiny
    params = pipe.stages[0]["params"]
    assert params["final"]["head"]["w"] is params["embeddings"]["wte"]
    distinct = {id(leaf): leaf for leaf in jax.tree_util.tree_leaves(params)}
    assert sum(leaf.size for leaf in distinct.values()) \
        == costs.held_parameters(config) + 2
    assert float(params["embeddings"]["factor"]) == 12.0
    assert float(params["final"]["factor"]) == 0.125
    with np.load(path) as tensors:
        assert "lm_head.weight" not in tensors.files
        # 2 + 8 x (2 norms + 2 fused SwiGLU tensors) + 6 x 8 + 2 x 4
        assert len(tensors.files) == 2 + 8 * 4 + 6 * 8 + 2 * 4
    drawn = granite_hybrid.init_params(
        pipe.cfg, ShardConfig(1, 32, is_first=True, is_last=True))
    assert drawn["final"]["head"]["w"] is drawn["embeddings"]["wte"]


def test_the_loader_splits_the_fused_input_linear(tiny):
    config, path, pipe, _, _ = tiny
    runs = pipe.stages[0]["params"]["blocks"].runs
    with np.load(path) as tensors:
        fused = np.asarray(
            tensors["model.layers.2.shared_mlp.input_linear.weight"],
            np.float32)
    attention = runs[1]             # `MM*`: the second run, one block
    np.testing.assert_array_equal(attention["mlp"]["gate"][0], fused[:64])
    np.testing.assert_array_equal(attention["mlp"]["up"][0], fused[64:])


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_a_fresh_cache_holds_each_kinds_leaves_for_its_layers_only(size):
    entry = registry.get_model_entry(TINY if size == "tiny" else WHOLE)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    runs = kind_runs(granite_hybrid.FAMILY, cfg, stage)
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, 2, 32,
        leaves=granite_hybrid.cache_leaves(cfg), runs=runs))
    shapes = {name: leaf.shape for name, leaf in cache.items()}
    if size == "tiny":
        assert runs == (("mamba", 2), ("attention", 1), ("mamba", 3),
                        ("attention", 1), ("mamba", 1))
        assert shapes == {"k": (2, 2, 32, 16), "v": (2, 2, 32, 16),
                          "ssm_state": (6, 2, 4, 8, 8),
                          "ssm_conv": (6, 2, 3, 48),
                          "stats": (8, len(granite_hybrid.STATS), 2)}
    else:       # 36 layers of 2.10 MB of state a request, 4 of keys and values
        assert [count for _, count in runs] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
        assert shapes["ssm_state"] == (36, 2, 64, 64, 128)
        assert shapes["ssm_conv"] == (36, 2, 3, 4352)
        assert shapes["k"] == (4, 2, 32, 512)


def _counters():
    return {(name, phase): prom.REGISTRY.counter(
        f"pipeedge_{name}_total", "").value(phase=phase)
        for name in granite_hybrid.STATS for phase in ("prefill", "decode")}


def test_counters_of_one_batch_are_what_its_sizes_predict(tiny):
    _, _, pipe, ids, _ = tiny
    before = _counters()
    pipe.generate(ids[:, :21], 8)
    gained = {key: value - before[key] for key, value in _counters().items()}
    # 2 rows x 21 positions x 6 Mamba-2 layers, in spans of 8, 8 and 5
    assert gained["ssm_positions_chunked", "prefill"] == 2 * 21 * 6
    assert gained["ssm_positions_stepped", "prefill"] == 0
    assert gained["ssm_state_carries", "prefill"] == 2 * 6  # not the first
    assert gained["ssm_positions_chunked", "decode"] == 0
    assert gained["ssm_positions_stepped", "decode"] == 2 * 7 * 6
    assert gained["ssm_state_carries", "decode"] == 7 * 6
    # the CPU keeps the einsums and the jnp step
    assert gained["attend_fused_calls", "prefill"] == 0
    assert gained["ssm_steps_fused", "decode"] == 0
    gauge = prom.REGISTRY.gauge("pipeedge_cache_leaf_bytes", "")
    assert gauge.value(leaf="ssm_state") == 6 * 2 * 4 * 8 * 8 * 4
    assert gauge.value(leaf="k") == 2 * 2 * 32 * 16 * 4


# -- what it runs, and what it refuses by name ----------------------------------

def test_a_prefix_is_a_state_and_rows_broadcast_over_the_batch(tiny):
    _, _, pipe, ids, _ = tiny
    whole = np.asarray(pipe.generate(ids[:1, :21], 6))
    handle = pipe.precompute_prefix(ids[0, :13])
    suffix = np.repeat(ids[:1, 13:21], 3, axis=0)
    got = np.asarray(pipe.generate(suffix, 6, prefix=handle))
    for row in got:
        np.testing.assert_array_equal(row[8:], whole[0, 21:])


def test_tools_generate_takes_the_model_and_its_cut(capsys, monkeypatch):
    import sys
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import generate
    from pipeedge_tpu import utils
    monkeypatch.setattr(utils, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "generate.py", "-m", TINY + "@5,v60", "-b", "2", "--prompt-len",
        "12", "--new-tokens", "4", "--max-len", "32"])
    generate.main()
    assert "tokens" in capsys.readouterr().out


@pytest.mark.parametrize("asked", ["mesh", "sp_mesh", "ep_mesh",
                                   "tp_ep_mesh", "cache_bits", "forward",
                                   "kv_pages", "speculative", "spmd"])
def test_what_the_family_cannot_do_is_refused_by_name(asked):
    from jax.sharding import Mesh
    entry = registry.get_model_entry(TINY)
    _, params, stage = registry.module_shard_factory(TINY, None, 1, 32,
                                                     unroll=False)
    assert isinstance(params["blocks"], BlockRuns)
    if asked == "forward":
        with pytest.raises(NotImplementedError, match="runs of"):
            shard_apply(entry.family.FAMILY, entry.config, stage, params,
                        jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(NotImplementedError, match="granite_hybrid"):
            granite_hybrid.FAMILY.sublayer({}, 0, None, entry.config)
        with pytest.raises(NotImplementedError, match="granite_hybrid"):
            granite_hybrid.init_params(entry.config, ShardConfig(1, 2))
        return
    if asked == "spmd":
        from pipeedge_tpu.parallel.spmd_decode import SpmdDecodePipeline
        mesh = Mesh(np.array(jax.devices()[:1]), ("stage",))
        with pytest.raises(NotImplementedError, match="granite_hybrid"):
            SpmdDecodePipeline(entry.family.FAMILY, entry.config, [(1, 32)],
                               [params], mesh, max_len=32)
        return
    if asked in ("kv_pages", "speculative"):
        pipe = decode.DecodePipeline(entry.family.FAMILY, entry.config,
                                     [(1, 32)], [params], max_len=32)
    if asked == "kv_pages":
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import serve
        with pytest.raises(NotImplementedError, match="granite_hybrid"):
            serve._Service(pipe, kv_pages=4)
        return
    if asked == "speculative":
        from pipeedge_tpu.parallel.speculative import SpeculativeDecoder
        draft = decode.build_decode_pipeline("pipeedge/test-tiny-gpt2", None,
                                             max_len=32)
        for target, drafter in ((pipe, draft), (draft, pipe)):
            with pytest.raises(NotImplementedError,
                               match="granite_hybrid.*earlier position"):
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


def test_the_cells_model_is_a_decoder_the_clis_take():
    assert registry.decoder_model(WHOLE) == WHOLE
    assert registry.decoder_model(WHOLE + "@6,v4096") == WHOLE + "@6,v4096"
    entry = registry.get_model_entry(WHOLE)
    cfg = entry.config
    assert (entry.layers, cfg.num_hidden_layers, cfg.vocab_size) \
        == (160, 40, 100352)
    assert [i for i, kind in enumerate(cfg.layer_types)
            if kind == "attention"] == [5, 15, 25, 35]
    # the cell's prompt in whole spans; a span's chunk is the span where the
    # published chunk is longer; a span of 64 rows needs no chunks of rows
    assert 512 % cfg.prefill_chunk == 0 and cfg.linear_chunk == 256
    assert 64 * cfg.prefill_chunk * 2 * cfg.intermediate_size * 12 \
        // 2 <= 1 << 29
    # every parameter, by the loader's shapes: 3.191 G, the table once
    stage = ShardConfig(1, 160, is_first=True, is_last=True)
    params = jax.eval_shape(lambda: granite_hybrid._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params)) \
        - cfg.vocab_size * cfg.hidden_size - 2
    assert count == costs.held_parameters(_config(tiny=False)) == 3191396096
    with pytest.raises(ValueError, match="no cut"):
        registry.get_model_entry(WHOLE + "@41")
    with pytest.raises(ValueError, match="no cut"):
        registry.get_model_entry(WHOLE + "@4,e0+2")


@pytest.mark.parametrize("tiny_cut", [False, True])
def test_the_registry_holds_the_configurations_sizes(tiny_cut):
    """The widths, the pattern and the four constants are data of the
    configuration file; the program's registry entry holds the same."""
    config = _config(tiny=tiny_cut)
    cfg = registry.get_model_config(config["program_model"])
    assert list(cfg.layer_types) == config["layer_types"]
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.kv_heads, cfg.head_dim, cfg.vocab_size, cfg.ssm_heads,
            cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.conv_kernel,
            cfg.linear_chunk, cfg.intermediate_size, cfg.layer_norm_eps,
            cfg.scale_emb, cfg.residual_multiplier, cfg.attention_multiplier,
            cfg.logits_scaling, cfg.max_position_embeddings) == (
                config["hidden_size"], config["num_hidden_layers"],
                config["num_attention_heads"], config["num_key_value_heads"],
                config["hidden_size"] // config["num_attention_heads"],
                config["vocab_size"], config["mamba_n_heads"],
                config["mamba_d_head"], config["mamba_d_state"],
                config["mamba_n_groups"], config["mamba_d_conv"],
                config["mamba_chunk_size"],
                config["shared_intermediate_size"], config["rms_norm_eps"],
                config["embedding_multiplier"],
                config["residual_multiplier"],
                config["attention_multiplier"], config["logits_scaling"],
                config["max_position_embeddings"])
    assert config["tie_word_embeddings"] is True
    assert config["position_embedding_type"] == "nope"
    assert config["num_local_experts"] == 0 and config["reduced"] == []


# -- the benchmark's cost functions --------------------------------------------

def test_costs_against_the_published_counts():
    config = _config(tiny=False)
    # ISSUE 54's reckoning, which reproduces the published 3B
    assert costs.mamba_params(config) == 25847232           # 25.85 M
    assert costs.attention_params(config) == 10485760       # 10.49 M
    assert costs.swiglu_params(config) == 50331648          # 50.33 M
    assert round(costs.held_parameters(config) / 1e9, 2) == 3.19
    assert costs.layer_state_bytes_a_row(config) == 2097152
    assert costs.state_bytes_a_row(config) == 36 * (2097152 + 52224)
    assert costs.kv_bytes_a_token(config) == 16384
    # a step at 64 rows, 768 live positions: 17.1 GB, the state read once
    # and written once 57% of it
    step = costs.decode_step_bytes(config, 64, 768)
    assert round(step / 1e9, 1) == 17.1
    assert round(2 * 64 * 36 * 2097152 / step, 2) == 0.57
    assert costs.decode_step_bytes(config, 64, 768) \
        - costs.weight_bytes(config) \
        == 64 * (768 * 16384 + 2 * 36 * (2097152 + 52224))
    tiny = _config()
    assert costs.mamba_params(tiny) == 32 * (32 + 48 + 4) + 48 * 5 + 12 \
        + 32 + 32 * 32
    assert costs.held_parameters(tiny) == 6 * costs.mamba_params(tiny) \
        + 2 * (2 * 32 * 32 + 2 * 32 * 16) + 8 * (3 * 32 * 64 + 64) \
        + 100 * 32 + 32
