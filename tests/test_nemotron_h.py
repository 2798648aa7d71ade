"""The nemotron_h family (models/nemotron_h.py: blocks of ONE sublayer, a
Mamba-2 mixer whose state is a matrix a head beside a plain attention's keys
and values in one stage's cache, a chunked form for spans and the recurrence
for steps, experts without a gate matrix that work in a latent narrower than
the model, a chip's share of the experts and of the vocabulary) against the
benchmark's plain reference, on the CPU at `pipeedge/test-tiny-nemotron-h`,
with seeded weights in the published key scheme."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_nemotron_h as costs, weights
from benchmark.reference import nemotron_h as reference
from pipeedge_tpu.models import (ShardConfig, decoder, mamba2, nemotron_h,
                                 registry, stage_cache)
from pipeedge_tpu.models.shard import BlockRuns, kind_runs, shard_apply
from pipeedge_tpu.parallel import decode, expert
from pipeedge_tpu.telemetry import metrics as prom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "pipeedge/test-tiny-nemotron-h"
WHOLE = "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
CELL = WHOLE + "@11,e0+128,v32768"
LENGTH = 30


def _config(tiny=True, **over):
    name = "nemotron-3-super-120b-a12b.json"
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


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The benchmark's tiny cut, a share of the model (experts 0-1 of 8, a
    quarter; half the vocabulary), eight blocks `MEMM*EME` in one stage:
    (config, weights file, pipeline, ids [2, 30], reference logits, the
    reference's chosen experts a layer and row)."""
    config = _config()
    path = weights.write(config, 2 ** 31 + 7, str(
        tmp_path_factory.mktemp("nemotron_h") / "weights.npz"))
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.float32,
        model_file=path)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            size=(2, LENGTH))
    record = []
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids, record=record)
    return config, path, pipe, ids, wanted, record


# float32 program against float32 reference: they differ by the order of
# their sums (the chunked form against the recurrence a position, the sorted
# groups against a token's experts by index; 1.8e-7 of the logits' range
# measured); 1e-5 leaves room for another BLAS and would fail a bfloat16
# product, a bfloat16 state or another expert chosen a hundred times over
TOLERANCE = 1e-5


# the tiny model prefills in spans of 8 and chunks of 4: within a span and
# not a multiple of the chunk (3), a span (8), across a span boundary and not
# a multiple of the chunk (13, 21), two spans (16)
@pytest.mark.parametrize("prompt_len", [3, 8, 13, 16, 21])
def test_spans_then_decode_match_the_reference(prompt_len, tiny):
    _, _, pipe, ids, wanted, _ = tiny
    got = _logits_through_the_cache(pipe, ids, prompt_len)
    assert _gap(got, wanted[:, prompt_len - 1:]) < TOLERANCE


def test_the_whole_model_matches_the_reference(tmp_path):
    """All 8 experts and the whole vocabulary: the uncut registry entry."""
    config = _config(n_routed_experts=8, vocab_size=100)
    path = weights.write(config, 11, str(tmp_path / "weights.npz"))
    pipe = decode.build_decode_pipeline(TINY, None, max_len=32,
                                        dtype=jnp.float32, model_file=path)
    ids = np.random.default_rng(4).integers(0, 100, size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)[:, 12:]
    assert _gap(_logits_through_the_cache(pipe, ids, 13), wanted) < TOLERANCE


def test_a_whole_prompt_prefill_is_the_spans(tiny):
    """The served path's prefill program (the whole prompt in one call, the
    state from zeros and not from the cache) leaves what the spans leave."""
    _, _, pipe, ids, wanted, _ = tiny
    stage = pipe.stages[0]
    data, cache = stage["prefill"](stage["params"],
                                   jnp.asarray(ids[:, :21], jnp.int32),
                                   pipe._fresh_caches(2)[0])
    _, spans = pipe._prefill(jnp.asarray(ids[:, :21], jnp.int32))
    assert _gap(np.asarray(data[:, -1]), wanted[:, 20]) < TOLERANCE
    for name in ("k", "v", "ssm_state", "ssm_conv"):
        np.testing.assert_allclose(cache[name], spans[0][name], atol=1e-6)


def test_bfloat16_weights_are_computed_on_in_float32(tiny):
    """The cell's precision: weights as stored, activations, state and cache
    float32. The scheme's values are bfloat16's, so nothing is rounded."""
    config, path, _, ids, wanted, _ = tiny
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


# -- Mamba-2: the chunked form, the one-token form, the reference's scan -------

def _ssm_inputs(length, seed=0, rows=2, groups=2, per=3, p=8, n=6):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    dt = jax.nn.softplus(draw(rows, length, groups, per) - 2.0)
    # decays from 0.2 to 0.999 a position over the heads
    rate = jnp.asarray(np.geomspace(1e-3, 16.0, groups * per),
                       jnp.float32).reshape(groups, per)
    return (draw(rows, length, groups, per, p), draw(rows, length, groups, n),
            draw(rows, length, groups, n), dt, -rate * dt,
            draw(rows, groups, per, p, n))


def _by_steps(x, bm, cm, dt, la, state):
    out = []
    for t in range(x.shape[1]):
        y, state = nemotron_h.ssm_step(x[:, t], bm[:, t], cm[:, t], dt[:, t],
                                       la[:, t], state)
        out.append(y)
    return jnp.stack(out, 1), state


@pytest.mark.parametrize("chunk, length", [(4, 8), (4, 11), (128, 160),
                                           (32, 32)])
@pytest.mark.parametrize("start", ["zero", "nonzero"])
def test_the_chunked_form_is_the_recurrence(start, chunk, length):
    """Across a chunk's edge, with a last chunk the span does not fill (the
    padding leaves the state), from a state the cache handed over."""
    x, bm, cm, dt, la, state = _ssm_inputs(length)
    if start == "zero":
        state = jnp.zeros_like(state)
    wanted, after = _by_steps(x, bm, cm, dt, la, state)
    got, left = nemotron_h.ssm_chunked(x, bm, cm, dt, la, state, chunk)
    np.testing.assert_allclose(got, wanted, atol=2e-5 * np.abs(wanted).max())
    np.testing.assert_allclose(left, after, atol=2e-5 * np.abs(after).max())
    if start == "nonzero":
        return
    # and both are the reference's scan over positions, a row at a time
    for row in range(x.shape[0]):
        heads = x.shape[2] * x.shape[3]
        y = reference._recurrence(
            x[row].reshape(length, heads, -1), bm[row], cm[row],
            dt[row].reshape(length, heads),
            jnp.exp(la[row]).reshape(length, heads), jnp.zeros(heads))
        np.testing.assert_allclose(
            got[row].reshape(y.shape), y, atol=2e-5 * np.abs(wanted).max())


def _mamba_block(seed=0):
    cfg = registry.get_model_config(TINY)
    stage = ShardConfig(1, 4, is_first=False, is_last=False)
    params = nemotron_h.init_params(cfg, stage, seed=seed)
    block = jax.tree_util.tree_map(lambda leaf: leaf[0], params["blocks"])
    assert "in_proj" in block
    return cfg, block


def _reader(state, tail):
    def read(name, first, rows):
        return {"ssm_state": state, "ssm_conv": tail}[name][
            first:first + rows]
    return read


@pytest.mark.parametrize("cut", [1, 3, 4, 5, 11])
def test_state_and_tail_cross_a_span_boundary(cut):
    """Spans of unequal length: the mixer over 12 positions at once against
    the same in two calls, the second from the state and the convolution's
    last three inputs the first left (a cut inside a chunk, at its edge, one
    position in)."""
    cfg, p = _mamba_block()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, 32)),
                    jnp.float32)
    state = jnp.zeros((2, 4, 8, 8), jnp.float32)
    tail = jnp.zeros((2, 3, nemotron_h.conv_channels(cfg)), jnp.float32)
    whole, s_whole, t_whole = nemotron_h.mamba(p, x, _reader(state, tail), cfg)
    first, state, tail = nemotron_h.mamba(p, x[:, :cut], _reader(state, tail),
                                          cfg)
    second, state, tail = nemotron_h.mamba(p, x[:, cut:],
                                           _reader(state, tail), cfg)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=1e-6)
    np.testing.assert_allclose(state, s_whole, atol=1e-6)
    np.testing.assert_array_equal(tail, t_whole)


def test_rows_in_groups_change_nothing(monkeypatch):
    """At real sizes a span's mixer runs the batch in groups of rows, each
    group's state read from the stack at its turn; forced here: one row a
    group."""
    cfg, p = _mamba_block()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 8, 32)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(4, 4, 8, 8)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(
        4, 3, nemotron_h.conv_channels(cfg))), jnp.float32)
    wanted = nemotron_h.mamba(p, x, _reader(state, tail), cfg)
    monkeypatch.setattr(decoder, "PRODUCT_BYTES",
                        8 * p["in_proj"].shape[0] * 12)
    calls = []

    def read(name, first, rows):
        calls.append(rows)
        return jax.lax.dynamic_slice_in_dim(
            {"ssm_state": state, "ssm_conv": tail}[name], first, rows)

    got = nemotron_h.mamba(p, x, read, cfg)
    assert set(calls) == {1}
    for one, other in zip(got, wanted):
        np.testing.assert_allclose(one, other, atol=1e-6)


# -- the experts: the router's choice, the latent, the shares -------------------

def test_the_chosen_experts_are_the_references_at_every_token(tiny):
    """Layer by layer through the tiny model: the program's router over the
    hidden states the reference's own layers produce chooses the reference's
    3 of 8 at every token, with its gates (sigmoid + correction bias, the
    kept scores over their sum, times 5)."""
    config, path, pipe, ids, _, record = tiny
    cfg = pipe.cfg
    assert len(record) == 2 * 3         # rows x expert layers
    with np.load(path) as tensors:
        for entry in record:
            root = f"backbone.layers.{entry['layer']}.mixer.gate."
            router = {"w": jnp.asarray(tensors[root + "weight"],
                                       jnp.float32).T,
                      "bias": jnp.asarray(
                          tensors[root + "e_score_correction_bias"],
                          jnp.float32)}
            tokens = jnp.asarray(np.random.default_rng(
                entry["layer"]).normal(size=(40, 32)), jnp.float32)
            experts, gates = expert.topk_route(router, tokens, cfg)
            chosen, weight = reference.route(
                tokens, router["w"].T, router["bias"], 3, 5.0)
            np.testing.assert_array_equal(experts, chosen)
            np.testing.assert_allclose(gates, weight, rtol=1e-5)
            assert np.allclose(np.asarray(gates).sum(-1), 5.0, rtol=1e-5)
    # and through the model the logits agree at 1e-7 of their range
    # (`test_spans_then_decode_match_the_reference`), which one other expert
    # at one token would move by 1e-2
    assert all(entry["experts"].shape == (LENGTH, 3) for entry in record)


def _expert_layer(seed=2):
    cfg = registry.get_model_config(TINY)
    rng = np.random.default_rng(seed)
    d, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_experts
    latent, fs = cfg.moe_latent_size, cfg.shared_expert_width

    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, size=shape), jnp.float32)

    params = {"router": {"w": mat(d, e), "bias": mat(e)},
              "latent": {"down": mat(latent, d), "up": mat(d, latent)},
              "experts": {"up": mat(e, f, latent), "down": mat(e, latent, f)},
              "shared": {"up": mat(fs, d), "down": mat(d, fs)}}
    return cfg, params, mat(2, 5, d)


def _plain_layer(cfg, params, x, **left_out):
    """The uncut layer as the reference has it: a token's experts by index
    in the latent, the way back up, the shared expert on the full width."""
    tokens = x.reshape(-1, x.shape[-1])
    bias = params["router"]["bias"] * (0 if "bias" in left_out else 1)
    experts, gates = reference.route(tokens, params["router"]["w"].T, bias,
                                     cfg.num_experts_per_tok,
                                     1.0 if "scale" in left_out else 5.0)
    c = tokens @ params["latent"]["down"].T
    r = np.zeros(c.shape, np.float32)
    for t in range(tokens.shape[0]):
        for e, gate in zip(np.asarray(experts[t]), np.asarray(gates[t])):
            r[t] += gate * np.asarray(reference.expert(
                c[t:t + 1], params["experts"]["up"][e],
                params["experts"]["down"][e]))[0]
    out = r @ params["latent"]["up"].T + reference.expert(
        tokens, params["shared"]["up"], params["shared"]["down"])
    return np.asarray(out).reshape(x.shape)


def _layer_delta(cfg, params, x):
    """The family's expert sublayer: the routed part through
    `topk_ffn_delta`, the shared expert through `decoder.dense_ffn`."""
    routed, stats = expert.topk_ffn_delta(
        {name: leaf for name, leaf in params.items() if name != "shared"},
        x, cfg)
    return routed, decoder.dense_ffn(params["shared"], x, cfg.expert_act), \
        stats


def test_four_shares_of_two_experts_and_the_shared_once_add_up():
    cfg, params, x = _expert_layer()
    wanted = _plain_layer(cfg, params, x)
    routed, shared, stats = _layer_delta(cfg, params, x)
    np.testing.assert_allclose(routed + shared, wanted, atol=1e-5)
    assert stats[0] == 2 * 5 * cfg.num_experts_per_tok
    # handed the shared expert, `topk_ffn_delta` adds the same one
    inside, _ = expert.topk_ffn_delta(params, x, cfg)
    np.testing.assert_allclose(inside, wanted, atol=1e-5)
    total, assigned = shared, 0.0       # every chip computes it: counted once
    for first in range(0, 8, 2):
        mine = dict(params, experts={
            name: leaf[first:first + 2]
            for name, leaf in params["experts"].items()})
        share = dataclasses.replace(cfg, held_experts=(first, 2))
        delta, _, counts = _layer_delta(share, mine, x)
        total, assigned = total + delta, assigned + float(counts[0])
    np.testing.assert_allclose(total, wanted, atol=1e-5)
    assert assigned == float(stats[0])


@pytest.mark.parametrize("left_out", ["bias", "scale"])
def test_the_correction_bias_and_the_scale_of_five_are_there(left_out):
    cfg, params, x = _expert_layer()
    routed, shared, _ = _layer_delta(cfg, params, x)
    wrong = _plain_layer(cfg, params, x, **{left_out: True})
    assert np.abs(np.asarray(routed + shared) - wrong).max() > 1e-2


def test_an_expert_is_two_matrices_and_a_squared_relu():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8)), jnp.float32)
    up = jnp.asarray(np.random.default_rng(1).normal(size=(5, 8)), jnp.float32)
    down = jnp.asarray(np.random.default_rng(2).normal(size=(8, 5)),
                       jnp.float32)
    got = expert._expert_ffn(x, {"up": up, "down": down}, "relu2")
    wanted = np.square(np.maximum(np.asarray(x) @ np.asarray(up).T, 0)) \
        @ np.asarray(down).T
    np.testing.assert_allclose(got, wanted, rtol=1e-5, atol=1e-5)
    assert expert.expert_names({"up": up, "down": down}) == ("up", "down")
    assert expert.expert_names({"gate": up, "up": up, "down": down}) \
        == ("gate", "up", "down")


# -- each assumed equation shows when left out ---------------------------------

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
    ("mixer.D", 0.0), ("mixer.dt_bias", 0.0), ("conv1d.bias", 0.0)])
def test_each_tensor_left_out_fails_the_comparison(suffix, value, tiny,
                                                   tmp_path):
    """`D`, `dt_bias` and the convolution's bias: the reference on weights
    without one is no longer the program's."""
    config, path, pipe, ids, wanted, _ = tiny
    got = _logits_through_the_cache(pipe, ids, 13)
    with np.load(_edited(path, tmp_path, **{suffix: value})) as tensors:
        without = reference.forward(config, tensors, ids)[:, 12:]
    assert _gap(got, wanted[:, 12:]) < TOLERANCE
    assert _gap(got, without) > 10 * TOLERANCE


@pytest.mark.parametrize("left_out", ["gate_before_norm", "norm_groups"])
def test_each_equation_left_out_fails_the_comparison(left_out, tiny,
                                                     monkeypatch):
    """The gate before the norm and the norm a group of heads: the
    reference with one changed is no longer the program's. (The correction
    bias and the scale of 5 are held at the layer, above: at the tiny
    widths a squared ReLU of the pool's 0.02 leaves the routed experts 1e-5
    of the logits.)"""
    config, path, pipe, ids, wanted, _ = tiny
    got = _logits_through_the_cache(pipe, ids, 13)
    if left_out == "norm_groups":       # one norm over all the lanes
        plain = reference._mamba_output
        monkeypatch.setattr(reference, "_mamba_output", lambda *a, **kw:
                            plain(*a, **dict(kw, groups=1)))
    else:                               # the norm first, then the gate

        def gate_after(x, y, z, norm_w, out_proj, eps, groups):
            length = x.shape[0]
            y = y.reshape(length, groups, -1)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
            y = y.reshape(length, -1) * norm_w * jax.nn.silu(z)
            return x + y @ out_proj.T
        monkeypatch.setattr(reference, "_mamba_output", gate_after)
    with np.load(path) as tensors:
        without = reference.forward(config, tensors, ids)[:, 12:]
    assert _gap(got, wanted[:, 12:]) < TOLERANCE
    assert _gap(got, without) > 10 * TOLERANCE


# -- the cache: a whole leaf that is updated a run at a time --------------------

@pytest.mark.parametrize("size", ["tiny", "published"])
def test_a_fresh_cache_holds_each_kinds_leaves_for_its_layers_only(size):
    model = TINY if size == "tiny" else CELL
    entry = registry.get_model_entry(model)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    runs = kind_runs(nemotron_h.FAMILY, cfg, stage)
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, 2, 32, leaves=nemotron_h.cache_leaves(cfg),
        runs=runs))
    shapes = {name: leaf.shape for name, leaf in cache.items()}
    if size == "tiny":
        assert runs == (("mamba", 1), ("experts", 1), ("mamba", 2),
                        ("attention", 1), ("experts", 1), ("mamba", 1),
                        ("experts", 1))
        assert shapes == {"k": (1, 2, 32, 16), "v": (1, 2, 32, 16),
                          "ssm_state": (4, 2, 4, 8, 8),
                          "ssm_conv": (4, 2, 3, 64),
                          "stats": (8, len(nemotron_h.STATS), 2)}
    else:       # eleven runs of one block; 4.19 MB of state a request a layer
        assert [kind for kind, _ in runs] == [
            {"M": "mamba", "*": "attention", "E": "experts"}[m]
            for m in "MEMEMEM*EME"]
        assert all(count == 1 for _, count in runs)
        assert shapes["ssm_state"] == (5, 2, 128, 64, 128)
        assert shapes["ssm_conv"] == (5, 2, 3, 10240)
        assert shapes["k"] == (1, 2, 32, 256)
        assert 128 * 64 * 128 * 4 == 4194304


@pytest.mark.parametrize("model", [TINY, "pipeedge/test-tiny-qwen3-next"])
def test_a_state_written_a_run_at_a_time_is_the_state_gathered(model,
                                                               monkeypatch):
    """`decode.WHOLE_IN_PLACE_BYTES`: a `whole` leaf's rows written into the
    stack as each run leaves them (the cell's 537 MB a layer) and gathered
    until the end (a tiny state, the siblings') leave one cache and one
    output, here and in a sibling that keeps a state. The choice is a
    leaf's and not a run's: with the limit between the rows of the tiny
    twin's `M` and `MM` runs (2,048 and 4,096 B of state, 1,536 and 3,072 B
    of tail) the short runs' rows are written too."""
    ids = jnp.asarray(np.random.default_rng(5).integers(0, 50, size=(2, 11)),
                      jnp.int32)
    out = []
    for limit in (decode.WHOLE_IN_PLACE_BYTES, 3000, 0):
        monkeypatch.setattr(decode, "WHOLE_IN_PLACE_BYTES", limit)
        pipe = decode.build_decode_pipeline(model, None, max_len=32)
        data, caches = pipe._prefill(ids)
        data, caches = pipe.extend(ids[:, :1], caches, 11)
        out.append((np.asarray(data), {
            name: np.asarray(leaf) for name, leaf in caches[0].items()}))
    for data, cache in out[1:]:
        np.testing.assert_array_equal(out[0][0], data)
        assert sorted(out[0][1]) == sorted(cache)
        for name, leaf in out[0][1].items():
            np.testing.assert_array_equal(leaf, cache[name])


@pytest.mark.parametrize("placed", [False, True], ids=["gathered", "placed"])
@pytest.mark.parametrize("model, mamba_layers", [(TINY + "@3", 2), (TINY, 4)],
                         ids=["runs-of-one", "an-MM-run"])
def test_a_steps_state_through_the_kernel_is_the_jnp_steps(
        model, mamba_layers, placed, monkeypatch):
    """`nemotron_h.state_kernel_mode`: a prefill and eight steps with the
    state kernel (`ops/ssm_step.py`, interpret mode) against the same with
    the jnp step, in a stage whose Mamba-2 runs are of one block (`MEM`) and
    in one with a run of two (`MEMM*EME`), with `WHOLE_IN_PLACE_BYTES` on
    either side of the twin's leaf. Where the driver does not place the leaf
    no step takes the kernel and `ssm_steps_fused` stays 0; where it does,
    every stepped position of every Mamba-2 layer does, the run of two
    unrolled so that the second block takes the stack the first hands back;
    `ssm_positions_stepped` counts the same either way."""
    if placed:
        monkeypatch.setattr(decode, "WHOLE_IN_PLACE_BYTES", 0)
    rows, prompt, steps = 2, 11, 8
    ids = np.random.default_rng(11).integers(0, 50, size=(rows, prompt + steps))
    fused, stepped = (nemotron_h.STATS.index(name) for name in (
        "ssm_steps_fused", "ssm_positions_stepped"))
    out = []
    for mode in (None, "interpret"):
        monkeypatch.setattr(mamba2, "_kernel_mode", lambda mode=mode: mode)
        pipe = decode.build_decode_pipeline(model, None, max_len=32)
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
        if leaf.size:
            assert _gap(fused_cache[name], leaf) < 1e-6, name


def _counters():
    return {(name, phase): prom.REGISTRY.counter(
        f"pipeedge_{name}_total", "").value(phase=phase)
        for name in nemotron_h.STATS for phase in ("prefill", "decode")}


def test_counters_of_one_batch_are_what_its_sizes_predict(tiny):
    _, _, pipe, ids, _, _ = tiny
    before = _counters()
    pipe.generate(ids[:, :21], 8)
    gained = {key: value - before[key] for key, value in _counters().items()}
    # 2 rows x 21 positions x 4 Mamba-2 layers, in spans of 8, 8 and 5
    assert gained["ssm_positions_chunked", "prefill"] == 2 * 21 * 4
    assert gained["ssm_positions_stepped", "prefill"] == 0
    assert gained["ssm_state_carries", "prefill"] == 2 * 4  # not the first
    assert gained["ssm_positions_chunked", "decode"] == 0
    assert gained["ssm_positions_stepped", "decode"] == 2 * 7 * 4
    assert gained["ssm_state_carries", "decode"] == 7 * 4
    # the expert layers alone count the five
    assert gained["moe_layer_calls", "prefill"] == 3 * 3
    assert gained["moe_layer_calls", "decode"] == 7 * 3
    # the CPU keeps the einsums (`decoder.attend_masked`)
    assert gained["attend_fused_calls", "prefill"] == 0
    assert gained["attend_fused_calls", "decode"] == 0
    # 3 of 8 a token, 2 of 8 held: under one held assignment a token a layer
    assert 0 < gained["moe_assignments", "prefill"] <= 2 * 21 * 3 * 3
    gauge = prom.REGISTRY.gauge("pipeedge_cache_leaf_bytes", "")
    assert gauge.value(leaf="ssm_state") == 4 * 2 * 4 * 8 * 8 * 4
    assert gauge.value(leaf="ssm_conv") == 4 * 2 * 3 * 64 * 4


# -- what it runs, and what it refuses by name ----------------------------------

def test_a_prefix_is_a_state_and_rows_broadcast_over_the_batch(tiny):
    _, _, pipe, ids, _, _ = tiny
    whole = np.asarray(pipe.generate(ids[:1, :21], 6))
    handle = pipe.precompute_prefix(ids[0, :13])
    suffix = np.repeat(ids[:1, 13:21], 3, axis=0)
    got = np.asarray(pipe.generate(suffix, 6, prefix=handle))
    for row in got:
        np.testing.assert_array_equal(row[8:], whole[0, 21:])


def test_the_dense_served_path_runs_it(tiny):
    """`tools/serve.py` without pages: the wave batcher over per-request
    caches, chunked prefill included, token for token."""
    from pipeedge_tpu.parallel.batcher import ContinuousBatcher
    _, _, pipe, ids, _, _ = tiny
    prompts = [ids[:1, :7], ids[1:, :13], ids[:1, 5:10]]
    batcher = ContinuousBatcher(pipe, max_active=2, chunk_tokens=4)
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
    monkeypatch.setattr(utils, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "generate.py", "-m", TINY + "@5,e2+4,v60", "-b", "2", "--prompt-len",
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
        with pytest.raises(NotImplementedError, match="nemotron_h"):
            nemotron_h.FAMILY.sublayer({}, 0, None, entry.config)
        with pytest.raises(NotImplementedError, match="nemotron_h"):
            nemotron_h.init_params(entry.config, ShardConfig(1, 2))
        return
    if asked == "spmd":
        from pipeedge_tpu.parallel.spmd_decode import SpmdDecodePipeline
        mesh = Mesh(np.array(jax.devices()[:1]), ("stage",))
        with pytest.raises(NotImplementedError, match="nemotron_h"):
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
        with pytest.raises(NotImplementedError, match="nemotron_h"):
            serve._Service(pipe, kv_pages=4)
        return
    if asked == "speculative":
        from pipeedge_tpu.parallel.speculative import SpeculativeDecoder
        draft = decode.build_decode_pipeline("pipeedge/test-tiny-gpt2", None,
                                             max_len=32)
        for target, drafter in ((pipe, draft), (draft, pipe)):
            with pytest.raises(NotImplementedError,
                               match="nemotron_h.*earlier position"):
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
    assert registry.decoder_model(WHOLE) == WHOLE
    entry = registry.get_model_entry(CELL)
    cfg = entry.config
    # a block of one sublayer counts four in `-pt`'s numbers, as every block
    assert (entry.layers, cfg.num_hidden_layers, cfg.held_experts,
            cfg.n_experts, cfg.vocab_size) == (44, 11, (0, 128), 512, 32768)
    whole = registry.get_model_config(WHOLE)
    assert [sum(kind == name for kind in whole.layer_types)
            for name in ("mamba", "attention", "experts")] == [40, 8, 40]
    # the cell's prompt in whole spans; a span's chunk is the span where the
    # published chunk is longer (the compiler on the span's size: PERF.md)
    assert 256 % cfg.prefill_chunk == 0 and cfg.linear_chunk == 128
    # every parameter of the cut, by the loader's shapes: 4.648 G
    stage = ShardConfig(1, 44, is_first=True, is_last=True)
    params = jax.eval_shape(lambda: nemotron_h._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert count == costs.held_parameters(_config(tiny=False)) == 4648163712
    # the step's tile: 2,816 assignments in groups of 5.5 +- 2.3
    assert expert.expert_tile(128, 22, 512) == 16
    assert expert.grouped_layout(16) == (16, True)
    with pytest.raises(ValueError, match="no cut"):
        registry.get_model_entry(WHOLE + "@89")


@pytest.mark.parametrize("tiny_cut", [False, True])
def test_the_registry_holds_the_configurations_sizes(tiny_cut):
    """The widths and the pattern are data of the configuration file; the
    program's registry entry holds the same."""
    config = _config(tiny=tiny_cut)
    cfg = registry.get_model_config(config["program_model"])
    letters = {"mamba": "M", "attention": "*", "experts": "E"}
    assert "".join(letters[kind] for kind in cfg.layer_types) \
        == config["hybrid_override_pattern"]
    assert cfg.num_hidden_layers == config["num_hidden_layers"]
    assert cfg.held_experts == (config["experts_held_from"],
                                config["n_routed_experts"])
    assert cfg.n_experts == config["published"]["n_routed_experts"]
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.ssm_heads, cfg.ssm_head_dim,
            cfg.ssm_state, cfg.ssm_groups, cfg.conv_kernel, cfg.linear_chunk,
            cfg.moe_intermediate_size, cfg.moe_latent_size,
            cfg.shared_expert_width, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.layer_norm_eps,
            cfg.norm_topk_prob) == tuple(config[key] for key in (
                "hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "vocab_size", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
                "moe_intermediate_size", "moe_latent_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "layer_norm_epsilon",
                "norm_topk_prob"))
    assert (cfg.expert_act, cfg.router, cfg.gate_sum_eps) \
        == (config["mlp_hidden_act"], "sigmoid", 1e-20)
    # the program has no rotation: a file that asks for one is refused by
    # the reference, so the two cannot part without a sound
    assert config["attn_use_rope"] is False
    with pytest.raises(ValueError, match="attn_use_rope"):
        reference.forward(dict(config, attn_use_rope=True), {},
                          np.zeros((1, 4), np.int64))


def test_the_loader_reads_the_published_keys_into_init_params_shapes(tiny):
    config, path, _, _, _, _ = tiny
    entry = registry.get_model_entry(config["program_model"])
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    with np.load(path) as tensors:
        keys = set(tensors.files)
        loaded = nemotron_h.load_params(entry.config, stage, tensors)
        wider = dataclasses.replace(entry.config, moe_latent_size=24)
        with pytest.raises(ValueError, match=r"experts\.0\.up_proj\.weight"):
            nemotron_h.load_params(wider, stage, tensors)
    drawn = nemotron_h.init_params(entry.config, stage)
    shapes = jax.tree_util.tree_map(lambda leaf: (leaf.shape, leaf.dtype),
                                    (loaded, drawn))
    assert shapes[0] == shapes[1]
    for key in ("backbone.layers.0.mixer.conv1d.bias",
                "backbone.layers.0.mixer.A_log",
                "backbone.layers.1.mixer.gate.e_score_correction_bias",
                "backbone.layers.1.mixer.fc2_latent_proj.weight",
                "backbone.layers.1.mixer.experts.1.down_proj.weight",
                "backbone.layers.4.mixer.o_proj.weight",
                "backbone.norm_f.weight", "lm_head.weight"):
        assert key in keys
    assert "backbone.layers.1.mixer.experts.2.up_proj.weight" not in keys
    assert not any(key.startswith("mtp.") for key in keys)
    # 3 + 8 norms + 4 Mamba-2 x 8 + 4 + 3 expert layers x (4 + 2 x 2 + 2)
    assert len(keys) == 3 + 8 + 4 * 8 + 4 + 3 * 10
    # the decays are spread: a head's a position from 0.2 to 0.999
    with np.load(path) as tensors:
        a = np.exp(np.asarray(tensors["backbone.layers.0.mixer.A_log"],
                              np.float32))
        dt = np.log1p(np.exp(np.asarray(
            tensors["backbone.layers.0.mixer.dt_bias"], np.float32)))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 9e-4 <= dt.min() and dt.max() <= 0.11


# -- the benchmark's cost functions --------------------------------------------

def test_costs_against_the_published_counts():
    config = _config(tiny=False)
    # ISSUE 47's reckoning, which reproduces 120.7 G and 12.2 G a token
    assert costs.mamba_params(config) == 109635968
    assert costs.attention_params(config) == 35651584
    assert costs.expert_params(config) == 5505024
    assert costs.expert_layer_fixed_params(config) == 54526464
    whole = dict(config, num_hidden_layers=88, n_routed_experts=512,
                 vocab_size=131072)
    assert round(costs.held_parameters(whole) / 1e9, 1) == 120.7
    assert round((costs.token_product_flops(whole, 22) / 2
                  + 131072 * 4096) / 1e9, 1) == 12.2
    assert costs.state_bytes_a_row(config) == 5 * (4194304 + 122880)
    assert costs.kv_bytes_a_token(config) == 2048
    assert costs.expected_held_a_token(config) == 5.5
    # a step at 128 rows: 14.7 GB, the state read once and written once 38%
    step = costs.decode_step_bytes(config, 128, 512, 128)
    assert round(step / 1e9, 1) == 14.7
    assert round(2 * 128 * costs.state_bytes_a_row(config) / step, 2) == 0.38
    # a state read twice or copied is not in the count
    assert costs.decode_step_bytes(config, 128, 512, 128) \
        - costs.weight_bytes(config, 128) \
        == 128 * (512 * 2048 + 2 * 21585920)
    tiny = _config()
    assert costs.mamba_params(tiny) == 32 * (32 + 64 + 4) + 64 * 5 + 12 \
        + 32 + 32 * 32
    assert costs.held_parameters(tiny) == 4 * costs.mamba_params(tiny) \
        + (2 * 32 * 32 + 2 * 32 * 16) \
        + 3 * (32 * 8 + 8 + 2 * 32 * 16 + 2 * 32 * 24 + 2 * 2 * 16 * 16) \
        + 8 * 32 + 2 * 32 * 50 + 32
