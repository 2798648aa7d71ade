"""The qwen3_next family (models/qwen3_next.py: Gated DeltaNet layers whose
state is a matrix a head beside gated full-attention layers' keys and values
in one stage's cache, a chunked delta rule for spans and the recurrence for
steps, many small experts beside a gated shared one, a chip's share of the
experts and of the vocabulary) against the benchmark's plain reference, on
the CPU at `pipeedge/test-tiny-qwen3-next`, with seeded weights in the
published key scheme."""
import collections
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_qwen3_next as costs, weights
from benchmark.reference import qwen3_next as reference
from pipeedge_tpu.models import (ShardConfig, decoder, qwen3_next, registry,
                                 stage_cache)
from pipeedge_tpu.models.shard import BlockRuns, kind_runs, shard_apply
from pipeedge_tpu.parallel import decode, expert
from pipeedge_tpu.telemetry import metrics as prom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "pipeedge/test-tiny-qwen3-next"
CELL = "Qwen/Qwen3-Next-80B-A3B-Instruct@4,e0+256,v75968"
LENGTH = 30


def _config(tiny=True, **over):
    name = "qwen3-next-80b-a3b-instruct.json"
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
    """The benchmark's tiny cut, a share of the model (experts 0-3 of 8,
    half the vocabulary), eight blocks in one stage: (config, weights file,
    pipeline, ids [2, 30], reference logits)."""
    config = _config()
    path = weights.write(config, 2 ** 31 + 7, str(
        tmp_path_factory.mktemp("qwen3next") / "weights.npz"))
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.float32,
        model_file=path)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)
    return config, path, pipe, ids, wanted


# float32 program against float32 reference: they differ by the order of
# their sums (the chunked form against the recurrence a position, 1e-7 of
# the logits' range measured); 1e-5 leaves room for another BLAS and would
# fail a bfloat16 product or a bfloat16 state (2e-3) two hundred times over
TOLERANCE = 1e-5


# the tiny model prefills in spans of 8 and chunks of 4: within a span and
# not a multiple of the chunk (3), a span (8), across a span boundary and
# not a multiple of the chunk (13, 21), two and three spans (16, 24)
@pytest.mark.parametrize("prompt_len", [3, 8, 13, 16, 21, 24])
def test_spans_then_decode_match_the_reference(prompt_len, tiny):
    _, _, pipe, ids, wanted = tiny
    got = _logits_through_the_cache(pipe, ids, prompt_len)
    wanted = wanted[:, prompt_len - 1:]
    assert np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


def test_the_whole_model_matches_the_reference(tmp_path):
    """All 8 experts and the whole vocabulary: the uncut registry entry."""
    config = _config(num_experts=8, vocab_size=100)
    path = weights.write(config, 11, str(tmp_path / "weights.npz"))
    pipe = decode.build_decode_pipeline(TINY, None, max_len=32,
                                        dtype=jnp.float32, model_file=path)
    ids = np.random.default_rng(4).integers(0, 100, size=(2, LENGTH))
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids)[:, 12:]
    got = _logits_through_the_cache(pipe, ids, 13)
    assert np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


def test_a_whole_prompt_prefill_is_the_spans(tiny):
    """The served path's prefill program (the whole prompt in one call, the
    state from zeros and not from the cache) leaves what the spans leave."""
    _, _, pipe, ids, wanted = tiny
    stage = pipe.stages[0]
    data, cache = stage["prefill"](stage["params"],
                                   jnp.asarray(ids[:, :21], jnp.int32),
                                   pipe._fresh_caches(2)[0])
    _, spans = pipe._prefill(jnp.asarray(ids[:, :21], jnp.int32))
    spread = wanted[:, 20].max() - wanted[:, 20].min()
    assert np.abs(np.asarray(data[:, -1]) - wanted[:, 20]).max() \
        <= TOLERANCE * spread
    for name in ("k", "v", "gdn_state", "gdn_conv"):
        np.testing.assert_allclose(cache[name], spans[0][name], atol=1e-5)


def test_queries_in_chunks_change_nothing(tiny, monkeypatch):
    """At real sizes a span's scores run in chunks of queries; forced here:
    two queries a chunk."""
    config, path, _, ids, wanted = tiny
    monkeypatch.setattr(decoder, "SCORE_BYTES", 2 * 2 * 2 * 40 * 4)
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
    activations, cache and state over them give the reference's logits."""
    config, path, _, ids, wanted = tiny
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=32, dtype=jnp.bfloat16,
        model_file=path)
    blocks = pipe.stages[0]["params"]["blocks"]
    assert blocks.runs[0]["experts"]["gate"].dtype == jnp.bfloat16
    cache = pipe._fresh_caches(2)[0]
    assert {cache[name].dtype for name in ("k", "gdn_state", "gdn_conv")} \
        == {jnp.dtype(jnp.float32)}
    got = _logits_through_the_cache(pipe, ids, 21)
    wanted = wanted[:, 20:]
    assert np.abs(got - wanted).max() \
        <= TOLERANCE * (wanted.max() - wanted.min())


# -- the delta rule ------------------------------------------------------------

def _delta_inputs(length, decay, seed=0, heads=3, dk=8, dv=6):
    rng = np.random.default_rng(seed)

    def mat(*shape):
        return rng.normal(size=shape).astype(np.float32)

    q, k = mat(2, length, heads, dk), mat(2, length, heads, dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    low, high = {"slow": (0.99, 0.9999), "fast": (0.05, 0.6),
                 "mixed": (0.3, 0.999)}[decay]
    g = np.log(rng.uniform(low, high, size=(2, length, heads))).astype(
        np.float32)
    beta = rng.uniform(0, 1, size=(2, length, heads)).astype(np.float32)
    return q, k, mat(2, length, heads, dv), beta, g


def _by_recurrence(q, k, v, beta, g, state):
    """The equations of the issue, a position at a time, in float64."""
    state = np.asarray(state, np.float64).copy()
    out = np.zeros(v.shape, np.float64)
    for t in range(q.shape[1]):
        state *= np.exp(g[:, t].astype(np.float64))[..., None, None]
        d = beta[:, t][..., None] * (v[:, t] - np.einsum(
            "bhkv,bhk->bhv", state, k[:, t]))
        state += np.einsum("bhk,bhv->bhkv", k[:, t], d)
        out[:, t] = np.einsum("bhkv,bhk->bhv", state, q[:, t])
    return out, state


# whole chunks; a padded one; the cell's chunk (its inverse by blocks), 2.5
@pytest.mark.parametrize("chunk, length", [(4, 8), (4, 11), (64, 160)])
@pytest.mark.parametrize("decay", ["slow", "fast", "mixed"])
@pytest.mark.parametrize("start", ["zero", "nonzero"])
def test_the_chunked_form_is_the_recurrence(start, decay, chunk, length):
    q, k, v, beta, g = _delta_inputs(length, decay)
    state = np.zeros((2, 3, 8, 6), np.float32)
    if start == "nonzero":
        state = np.random.default_rng(9).normal(size=state.shape).astype(
            np.float32)
    wanted, wanted_state = _by_recurrence(q, k, v, beta, g, state)
    got, got_state = qwen3_next.delta_chunked(
        *(jnp.asarray(x) for x in (q, k, v, beta, g, state)), chunk=chunk)
    np.testing.assert_allclose(got, wanted, atol=2e-5)
    np.testing.assert_allclose(got_state, wanted_state, atol=2e-5)
    # and the one-token form, a position at a time
    carried, stepped = jnp.asarray(state), []
    for t in range(length):
        o, carried = qwen3_next.delta_step(
            *(jnp.asarray(x[:, t]) for x in (q, k, v, beta, g)), carried)
        stepped.append(o)
    np.testing.assert_allclose(jnp.stack(stepped, 1), wanted, atol=2e-5)
    np.testing.assert_allclose(carried, wanted_state, atol=2e-5)


def test_a_padded_chunk_leaves_the_state_as_it_was():
    """Five positions in chunks of four: the second chunk holds one position
    and three of padding (beta 0, g 0), after which the state is what five
    positions left, and what a sixth position finds."""
    q, k, v, beta, g = _delta_inputs(6, "mixed", seed=4)
    state = np.random.default_rng(1).normal(size=(2, 3, 8, 6)).astype(
        np.float32)
    five = [jnp.asarray(x[:, :5]) for x in (q, k, v, beta, g)]
    _, after_five = qwen3_next.delta_chunked(*five, jnp.asarray(state), 4)
    _, wanted = _by_recurrence(q[:, :5], k[:, :5], v[:, :5], beta[:, :5],
                               g[:, :5], state)
    np.testing.assert_allclose(after_five, wanted, atol=2e-5)
    sixth, _ = qwen3_next.delta_step(
        *(jnp.asarray(x[:, 5]) for x in (q, k, v, beta, g)), after_five)
    np.testing.assert_allclose(
        sixth, _by_recurrence(q, k, v, beta, g, state)[0][:, 5], atol=2e-5)


@pytest.mark.parametrize("decade", [-6, -3, -1, 1])
def test_a_decay_is_exp_to_an_ulp_and_without_a_bias(decade):
    """`_exp`, the factor the one-token form applies a position after
    another: against float64, a decade of arguments at a time."""
    x = -np.logspace(decade - 1, decade, 20001).astype(np.float32)
    got = np.asarray(jax.jit(qwen3_next._exp)(jnp.asarray(x)), np.float64)
    wanted = np.exp(x.astype(np.float64))
    off = (got - wanted) / wanted
    assert np.abs(off).max() < 1.2e-7 and abs(off.mean()) < 1e-8
    assert float(qwen3_next._exp(jnp.float32(0.0))) == 1.0
    assert float(qwen3_next._exp(jnp.float32(-200.0))) == 0.0


def _inverse_by_rows(low):
    """The row substitution `_inverse_unit_lower` was before it took blocks,
    kept as its reference: a row at a time over `[..., C, C]` as it comes."""
    c = low.shape[-1]

    def row(i, x):
        mine = jax.lax.dynamic_slice_in_dim(x, i, 1, axis=-2)
        mine = mine + jnp.einsum("...ij,...jk->...ik", mine, x,
                                 precision=jax.lax.Precision.HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(x, mine, i, axis=-2)

    return jax.lax.fori_loop(1, c, row, -low) + jnp.eye(c, dtype=low.dtype)


def _strict_lower(kind, c, lead=(2, 2, 3)):
    """`L` [N, B, H, C, C] of a chunk. `close`: keys that lie close together,
    L near all ones, whose inverse is small and whose powers are binomials;
    `decays`: what `delta_chunked` builds, `(beta K) K^T * Gamma` of normed
    keys under the heads' decays; `zeros`: a padded chunk's (beta 0)."""
    if kind == "zeros":
        return np.zeros(lead + (c, c), np.float32)
    if kind == "close":
        return np.broadcast_to(
            np.tril(np.full((c, c), 0.98, np.float32), -1), lead + (c, c))
    rng = np.random.default_rng(c)
    k = rng.normal(size=lead + (c, 8))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = rng.uniform(0, 1, size=lead + (c, 1))
    gamma = np.cumsum(np.log(rng.uniform(0.3, 0.999, size=lead + (c,))), -1)
    decay = np.exp(gamma[..., :, None] - gamma[..., None, :])
    return np.tril((beta * k) @ np.swapaxes(k, -1, -2) * decay, -1).astype(
        np.float32)


@pytest.mark.parametrize("kind", ["close", "decays", "zeros"])
@pytest.mark.parametrize("c", [1, 4, 16, 24, 64])   # blocks of 1, 4, 16, 12, 16
def test_the_inverse_is_exact_where_a_series_would_not_be(c, kind):
    low = _strict_lower(kind, c)
    got = jax.jit(qwen3_next._inverse_unit_lower)(jnp.asarray(low))
    assert got.shape == low.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(
        got, np.linalg.inv(np.eye(c) + low.astype(np.float64)), atol=1e-5)
    np.testing.assert_allclose(got, _inverse_by_rows(jnp.asarray(low)),
                               atol=1e-5)
    # one matrix and no batch, as the leading axes are folded
    np.testing.assert_allclose(
        qwen3_next._inverse_unit_lower(jnp.asarray(low[0, 0, 0])),
        got[0, 0, 0], atol=1e-6)


@pytest.mark.parametrize("chunk, block", [(1, 1), (4, 4), (16, 16), (24, 12),
                                          (33, 33), (48, 12), (64, 16),
                                          (128, 16)])
def test_a_chunks_blocks_merge_in_pairs_to_the_whole(chunk, block):
    """One parameter read off the chunk: halved while even and above 16."""
    assert qwen3_next.inverse_block(chunk) == block
    width = block
    while width < chunk:
        width *= 2
    assert width == chunk


@pytest.mark.parametrize("size, block", [("tiny", 0), ("published", 16)])
def test_a_build_says_which_inverse_its_chunks_take(size, block):
    """`pipeedge_gdn_inverse_block{chunk}`: 0 where a chunk is one block (the
    tiny model's 4), the block's width where blocks merge (the cell's 64)."""
    cfg = registry.get_model_config(TINY if size == "tiny" else CELL)
    stage = ShardConfig(1, 4, is_first=False, is_last=False)
    jax.eval_shape(lambda: qwen3_next._assemble(
        dataclasses.replace(cfg, num_hidden_layers=1), stage,
        lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    assert qwen3_next._M_INVERSE_BLOCK.value(
        chunk=str(cfg.linear_chunk)) == block
    assert f'pipeedge_gdn_inverse_block{{chunk="{cfg.linear_chunk}"}}' \
        in prom.REGISTRY.render()


def _linear_block(seed=0):
    cfg = registry.get_model_config(TINY)
    stage = ShardConfig(1, 4, is_first=False, is_last=False)
    params = qwen3_next.init_params(
        dataclasses.replace(cfg, num_hidden_layers=1), stage, seed=seed)
    block = jax.tree_util.tree_map(lambda leaf: leaf[0], params["blocks"])
    rng = np.random.default_rng(seed)
    # weights large enough that every term matters
    block = {name: leaf if name in ("a_log", "dt_bias", "out_norm")
             else jax.tree_util.tree_map(
                 lambda w: jnp.asarray(rng.normal(0, 0.3, size=w.shape),
                                       jnp.float32), leaf)
             for name, leaf in block.items()}
    return cfg, block, jnp.asarray(rng.normal(size=(2, 14, cfg.hidden_size)),
                                   jnp.float32)


@pytest.mark.parametrize("cut", [1, 2, 5, 8])
def test_the_convolutions_tail_crosses_a_span_boundary(cut):
    """A span cut at any position, shorter than the convolution too: the
    second part takes the state and the last three inputs of the first."""
    cfg, block, x = _linear_block()
    channels = qwen3_next.conv_channels(cfg)
    state = jnp.zeros((2, cfg.linear_value_heads, cfg.linear_key_dim,
                       cfg.linear_value_dim))
    tail = jnp.zeros((2, cfg.linear_conv_kernel - 1, channels))
    whole, whole_state, whole_tail = qwen3_next.gated_delta_net(
        block, x, state, tail, cfg)
    first, state, tail = qwen3_next.gated_delta_net(
        block, x[:, :cut], state, tail, cfg)
    second, state, tail = qwen3_next.gated_delta_net(
        block, x[:, cut:], state, tail, cfg)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=1e-5)
    np.testing.assert_allclose(state, whole_state, atol=1e-5)
    np.testing.assert_allclose(tail, whole_tail, atol=1e-6)
    assert np.abs(np.asarray(whole_tail)).min() > 0     # three real inputs


# -- the gated attention -------------------------------------------------------

def test_partial_rotary_turns_the_first_quarter_in_halves():
    cfg = registry.get_model_config(TINY)       # 16 wide, 4 turned
    x = np.random.default_rng(0).normal(size=(1, 5, 2, 16)).astype(
        np.float32)
    pos = np.array([0, 1, 2, 7, 40])
    got = np.asarray(qwen3_next.partial_rotate(jnp.asarray(x),
                                               jnp.asarray(pos), cfg))
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    np.testing.assert_array_equal(got[:, 0], x[:, 0])       # position 0
    inv_freq = 1e7 ** (-np.arange(0, 4, 2) / 4.0)           # 1e7**(-2i/4)
    for s, at in enumerate(pos):
        cos, sin = np.cos(at * inv_freq), np.sin(at * inv_freq)
        x1, x2 = x[0, s, :, :2], x[0, s, :, 2:4]
        np.testing.assert_allclose(got[0, s, :, :2], x1 * cos - x2 * sin,
                                   atol=1e-5)
        np.testing.assert_allclose(got[0, s, :, 2:4], x2 * cos + x1 * sin,
                                   atol=1e-5)
    # and at the published size: 64 of 256, the model's theta
    real = registry.get_model_config(CELL)
    wide = np.ones((1, 1, 1, 256), np.float32)
    turned = np.asarray(qwen3_next.partial_rotate(
        jnp.asarray(wide), jnp.asarray([3]), real))
    assert (turned[..., 64:] == 1).all() and (turned[..., :64] != 1).all()


def test_the_gate_multiplies_the_heads_outputs():
    """The full mixer against the issue's equations, written out in numpy:
    zero-centred q/k norms, the rotation, causal GQA softmax, the heads'
    outputs times sigmoid(gate), o_proj."""
    cfg = registry.get_model_config(TINY)
    heads, groups, hd, d = 4, 2, 16, 32
    rng = np.random.default_rng(2)

    def mat(*shape):
        return rng.normal(0, 0.4, size=shape).astype(np.float32)

    p = {"q": {"w": mat(heads * hd, d)}, "gate": {"w": mat(heads * hd, d)},
         "k": {"w": mat(groups * hd, d)}, "v": {"w": mat(groups * hd, d)},
         "q_norm": mat(hd), "k_norm": mat(hd),
         "attn_out": {"w": mat(d, heads * hd)}}
    x = mat(1, 6, d)
    stack = {name: jnp.zeros((1, 1, 8, groups * hd)) for name in ("k", "v")}
    got, _, _, fused = qwen3_next.gated_attention(
        jax.tree_util.tree_map(jnp.asarray, p), jnp.asarray(x),
        stage_cache.LayerCache(stack, 0), 0, cfg, prefill=True)

    def norm(t, w):
        return t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-6) * (1 + w)

    def turn(t):
        return np.asarray(qwen3_next.partial_rotate(
            jnp.asarray(t[None]), jnp.arange(6), cfg))[0]

    q = turn(norm((x[0] @ p["q"]["w"].T).reshape(6, heads, hd), p["q_norm"]))
    k = turn(norm((x[0] @ p["k"]["w"].T).reshape(6, groups, hd),
                  p["k_norm"]))
    v = (x[0] @ p["v"]["w"].T).reshape(6, groups, hd)
    out = np.zeros((6, heads, hd))
    for h in range(heads):
        scores = q[:, h] @ k[:, h // 2].T / 4.0
        scores[np.triu_indices(6, 1)] = -np.inf
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        out[:, h] = probs / probs.sum(-1, keepdims=True) @ v[:, h // 2]
    gate = 1 / (1 + np.exp(-(x[0] @ p["gate"]["w"].T)))
    wanted = (out.reshape(6, -1) * gate) @ p["attn_out"]["w"].T
    np.testing.assert_allclose(got[0], wanted, atol=2e-5)
    assert fused == 0       # the CPU keeps the einsums


# a span of the gated layer at the cell's head (256 lanes, 2 KV groups; 2
# query heads a group here): (rows, span, window, pos)
SPANS = {
    # the live length is no multiple of the kernel's key block of 512
    "window_live_to_no_whole_block": (2, 128, 1024, 700),
    # a prompt's first span: its own rows alone, causal
    "own_rows_only": (2, 128, 0, 0),
    # the ladder's width past the live length: two dead blocks at the end
    "window_with_a_dead_end_of_whole_blocks": (1, 256, 2048, 1024),
}


def _span(rows, span, width, pos, seed=4, heads=4, groups=2, hd=256):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q = draw(rows, span, heads, hd)
    parts = [(tuple(draw(rows, span, hd) for _ in range(groups)),
              tuple(draw(rows, span, hd) for _ in range(groups)), True)]
    if width:
        parts.insert(0, (tuple(draw(rows, width, hd) for _ in range(groups)),
                         tuple(draw(rows, width, hd) for _ in range(groups)),
                         False))
    return q, parts, pos


@pytest.mark.parametrize("name", list(SPANS))
def test_a_span_of_the_gated_layer_takes_the_kernel_whole(name, monkeypatch):
    """`attend` over heads of 256 under the causal mask: the einsums in
    chunks of queries (`decoder.SCORE_BYTES`), the streaming kernel (in
    interpret mode here) in one call a KV group over the whole span, the
    same context to 1e-6 of its range."""
    q, parts, pos = _span(*SPANS[name])
    rows, span = q.shape[:2]
    keys = sum(part[0][0].shape[1] for part in parts)
    # the einsums walk the span in four chunks, each from its own offset
    monkeypatch.setattr(decoder, "SCORE_BYTES",
                        span // 4 * rows * 2 * keys * 4)
    want, took = qwen3_next.attend(q, parts, pos)
    assert took == 0
    calls = []
    kernel = decoder.masked_attention.attend
    monkeypatch.setattr(decoder.masked_attention, "attend",
                        lambda q, *a, **kw: calls.append(q.shape)
                        or kernel(q, *a, **kw))
    monkeypatch.setattr(decoder, "_fused_mode", lambda: "interpret")
    got, took = qwen3_next.attend(q, parts, pos)
    assert took == 1 and got.shape == want.shape
    assert calls == [(rows, 2, span, 256)] * 2      # one call a KV group
    assert np.isfinite(np.asarray(got)).all()
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap <= 1e-6 * float(jnp.max(want) - jnp.min(want))


def test_a_span_that_fills_no_tile_keeps_its_chunks(monkeypatch):
    """Where the seam says einsums (own rows that are no whole key block
    here), `attend` chunks by `decoder.SCORE_BYTES` as it did, on a backend
    that runs Mosaic too."""
    q, parts, pos = _span(1, 96, 0, 0)
    want, _ = qwen3_next.attend(q, parts, pos)
    monkeypatch.setattr(decoder, "_fused_mode", lambda: "interpret")
    monkeypatch.setattr(decoder, "SCORE_BYTES", 32 * 2 * 96 * 4)
    text = str(jax.make_jaxpr(lambda q: qwen3_next.attend(q, parts, pos)[0])(
        q))
    assert "pallas_call" not in text and "scan" in text
    got, took = qwen3_next.attend(q, parts, pos)
    assert took == 0
    np.testing.assert_allclose(got, want, atol=1e-5)


WIDE = "pipeedge/test-wide-qwen3-next"


@pytest.fixture
def wide(monkeypatch):
    """A period of four blocks whose full layer has the kernel's shapes:
    heads of 128 lanes, spans of 128 positions (256 rows a KV group)."""
    monkeypatch.setitem(registry._MODELS, WIDE, registry._qwen3_next(
        WIDE, "test-wide-qwen3-next.npz", 32, 4, 4, 2, 128, (2, 4, 8, 8, 4),
        vocab=100, max_pos=512, experts=8, expert_width=16, per_tok=2,
        span=128))
    return WIDE


def test_fused_calls_count_spans_by_full_layers_through_the_pipeline(
        wide, monkeypatch):
    """`pipeedge_attend_fused_calls_total{phase}` through `generate`: a
    prompt of three whole spans over one full layer takes the kernel three
    times (the first span too: its own rows alone), a fourth span of 40
    rows and every step keep the einsums; the tokens are the einsums'."""
    ids = np.random.default_rng(9).integers(0, 100, size=(2, 3 * 128 + 40))
    pipe = decode.build_decode_pipeline(wide, None, max_len=512)
    want = np.asarray(pipe.generate(ids, 4))
    monkeypatch.setattr(decoder, "_fused_mode", lambda: "interpret")
    pipe = decode.build_decode_pipeline(wide, None, max_len=512)
    before = _counters()
    got = np.asarray(pipe.generate(ids, 4))
    gained = {key: value - before[key] for key, value in _counters().items()}
    assert gained["attend_fused_calls", "prefill"] == 3 * 1
    assert gained["attend_fused_calls", "decode"] == 0
    assert gained["moe_layer_calls", "prefill"] == 4 * 4
    np.testing.assert_array_equal(got, want)


def test_a_step_traces_to_the_parents_equations_but_for_the_stats_leaf():
    """The decode step of the tiny model on the seam (`decoder.softmax_over`
    for the layer's own copy of the einsums) is the program it was before
    PR 46: every primitive as often as then (2,210 equations), but that a KV
    group's queries are sliced once and not once a key part (two full
    layers x two groups: 4 `slice` and 4 `squeeze` fewer), over a `stats`
    leaf one count wider; no kernel in it."""
    from test_lfm2 import _equations
    pipe = decode.build_decode_pipeline(TINY, None, max_len=32)
    entry = registry.get_model_entry(TINY)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    run = decode._make_stage_run(entry.family.FAMILY, entry.config, stage)
    cache = pipe._fresh_caches(2)[0]
    assert cache["stats"].shape == (8, len(qwen3_next.STATS), 2)
    assert qwen3_next.STATS[-1:] == decoder.ATTEND_STATS
    jaxpr = jax.make_jaxpr(
        lambda p, d, c, pos: run(p, d, c, pos, prefill=False, read_len=32))(
            pipe.stages[0]["params"],
            jax.ShapeDtypeStruct((2, 1), jnp.int32), cache,
            jax.ShapeDtypeStruct((), jnp.int32))
    names = _equations(jaxpr.jaxpr, collections.Counter())
    assert "pallas_call" not in names
    assert (names["slice"], names["squeeze"]) == (102 - 4, 104 - 4)
    assert sum(names.values()) - names["slice"] - names["squeeze"] == 2004
    assert {name: names[name] for name in (
        "dot_general", "exp", "reduce_max", "reduce_sum", "select_n",
        "concatenate", "div", "max", "dynamic_slice",
        "dynamic_update_slice", "scan", "while")} == {
            "dot_general": 67, "exp": 16, "reduce_max": 16, "reduce_sum": 47,
            "select_n": 159, "concatenate": 42, "div": 47, "max": 16,
            "dynamic_slice": 52, "dynamic_update_slice": 6, "scan": 12,
            "while": 8}


# -- the expert layer ----------------------------------------------------------

@pytest.mark.parametrize("case", ["spread", "ties"])
def test_router_is_the_sorted_top_k_of_the_softmax(case):
    cfg = registry.get_model_config(CELL)       # 512 outputs, 10 a token
    rng = np.random.default_rng(5)
    tokens = rng.normal(size=(40, 16)).astype(np.float32)
    w = rng.normal(size=(16, 512)).astype(np.float32)
    if case == "ties":      # equal columns: equal probabilities
        w[:, 1::2] = w[:, 0::2]
    experts, gates = expert.topk_route({"w": jnp.asarray(w)},
                                       jnp.asarray(tokens), cfg)
    logits = tokens.astype(np.float64) @ w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    for t in range(40):
        order = sorted(range(512),
                       key=lambda e: (-np.float32(probs[t, e]), e))[:10]
        assert sorted(np.asarray(experts[t]).tolist()) == sorted(order)
        kept = probs[t, np.asarray(experts[t])]
        np.testing.assert_allclose(gates[t], kept / kept.sum(), rtol=1e-5)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-5)
    # the reference's router makes the same choice
    chosen, weight = reference.route(jnp.asarray(tokens), jnp.asarray(w.T),
                                     10)
    np.testing.assert_array_equal(chosen, experts)
    np.testing.assert_allclose(weight, gates, rtol=1e-5)


def _expert_layer():
    cfg = registry.get_model_config(TINY)
    rng = np.random.default_rng(2)
    d, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_experts

    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, size=shape), jnp.float32)

    params = {"router": {"w": mat(d, e)},
              "experts": {"gate": mat(e, f, d), "up": mat(e, f, d),
                          "down": mat(e, d, f)},
              "shared": {"gate": mat(f, d), "up": mat(f, d),
                         "down": mat(d, f)},
              "shared_gate": mat(1, d)}
    return cfg, params, mat(2, 5, d)


def _plain_layer(cfg, params, x):
    """The uncut layer as the reference has it: the gated shared expert,
    and each chosen expert in turn."""
    tokens = x.reshape(-1, x.shape[-1])
    experts, gates = reference.route(tokens, params["router"]["w"].T,
                                     cfg.num_experts_per_tok)
    out = np.asarray(reference._shared(tokens, params["shared_gate"], *(
        params["shared"][name] for name in ("gate", "up", "down")))).copy()
    for t in range(tokens.shape[0]):
        for e, gate in zip(np.asarray(experts[t]), np.asarray(gates[t])):
            out[t] += gate * np.asarray(reference._swiglu(
                tokens[t:t + 1], *(params["experts"][name][e]
                                   for name in ("gate", "up", "down"))))[0]
    return out.reshape(x.shape)


def test_two_shares_of_four_experts_and_the_gated_shared_once_add_up():
    cfg, params, x = _expert_layer()
    whole, stats = expert.topk_ffn_delta(params, x, cfg)
    wanted = _plain_layer(cfg, params, x)
    np.testing.assert_allclose(whole, wanted, atol=1e-5)
    assert stats[0] == 2 * 5 * cfg.num_experts_per_tok
    # the gate is there: without it the shared expert counts in full
    ungated, _ = expert.topk_ffn_delta(
        {name: leaf for name, leaf in params.items()
         if name != "shared_gate"}, x, cfg)
    assert np.abs(np.asarray(ungated) - wanted).max() > 1e-2
    total, assigned = 0.0, 0.0
    for first in (0, 4):
        mine = {"router": params["router"], "experts": {
            name: leaf[first:first + 4]
            for name, leaf in params["experts"].items()}}
        if first == 0:      # every chip computes it alike: counted once
            mine.update(shared=params["shared"],
                        shared_gate=params["shared_gate"])
        share = dataclasses.replace(cfg, held_experts=(first, 4))
        delta, counts = expert.topk_ffn_delta(mine, x, share)
        total, assigned = total + delta, assigned + float(counts[0])
    np.testing.assert_allclose(total, wanted, atol=1e-5)
    assert assigned == float(stats[0])


# -- the cache of two geometries -----------------------------------------------

def _fresh_cache(model, rows, max_len):
    entry = registry.get_model_entry(model)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    runs = kind_runs(entry.family.FAMILY, entry.config, stage)
    return runs, jax.eval_shape(lambda: stage_cache.init_cache(
        entry.config, entry.config.num_hidden_layers, rows, max_len,
        leaves=qwen3_next.cache_leaves(entry.config), runs=runs))


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_a_fresh_cache_holds_each_kinds_leaves_for_its_layers_only(size):
    if size == "tiny":
        runs, cache = _fresh_cache(TINY, 2, 32)
        assert runs == (("linear", 3), ("full", 1), ("linear", 3),
                        ("full", 1))
        shapes = {"k": (2, 2, 32, 32), "v": (2, 2, 32, 32),
                  "gdn_state": (6, 2, 4, 8, 8), "gdn_conv": (6, 2, 3, 64),
                  "stats": (8, 9, 2)}
    else:       # the cell: one period, 8 rows, 32,768 positions
        runs, cache = _fresh_cache(CELL, 8, 32768)
        assert runs == (("linear", 3), ("full", 1))
        shapes = {"k": (1, 8, 32768, 512), "v": (1, 8, 32768, 512),
                  "gdn_state": (3, 8, 32, 128, 128),
                  "gdn_conv": (3, 8, 3, 8192), "stats": (4, 9, 2)}
    assert {name: leaf.shape for name, leaf in cache.items()} == shapes
    held = sum(leaf.size * leaf.dtype.itemsize
               for name, leaf in cache.items() if name != "stats")
    if size == "published":
        # keys and values in ONE layer, state in THREE; four layers of keys
        # and values would be 4.3 GB
        assert held == 8 * 32768 * 4096 + 8 * 6586368 == 1126432768
        config = _config(tiny=False)
        assert costs.kv_bytes_a_token(config) == 4096
        assert costs.state_bytes_a_row(config) == 6586368


def test_each_run_writes_its_own_kinds_layers(tiny):
    """Eight blocks in one stage: the linear runs at layers 0-2 and 3-5 of
    their leaves, the full ones at 0 and 1 of theirs, one scan a run."""
    _, _, pipe, ids, _ = tiny
    blocks = pipe.stages[0]["params"]["blocks"]
    assert isinstance(blocks, BlockRuns) and len(blocks.runs) == 4
    _, caches = pipe._prefill(jnp.asarray(ids[:, :13], jnp.int32))
    cache = caches[0]
    for layer in range(6):
        assert np.abs(np.asarray(cache["gdn_state"][layer])).max() > 0
        assert np.abs(np.asarray(cache["gdn_conv"][layer])).max() > 0
    for layer in range(2):
        rows = np.asarray(cache["k"][layer])
        assert np.abs(rows[:, :13]).min() > 0 and not rows[:, 13:].any()
    # the two full layers hold different rows, the six states differ
    assert np.abs(np.asarray(cache["k"][0] - cache["k"][1])).max() > 1e-3
    states = np.asarray(cache["gdn_state"]).reshape(6, -1)
    assert len({row.tobytes() for row in states}) == 6
    entry = registry.get_model_entry(TINY)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    run = decode._make_stage_run(entry.family.FAMILY, entry.config, stage)
    jaxpr = jax.make_jaxpr(
        lambda p, d, c, pos: run(p, d, c, pos, prefill=False, read_len=32))(
            pipe.stages[0]["params"], jax.ShapeDtypeStruct((2, 1), jnp.int32),
            cache, jax.ShapeDtypeStruct((), jnp.int32))
    names = collections.Counter(eqn.primitive.name
                                for eqn in jaxpr.jaxpr.eqns)
    assert names["scan"] == 4


def test_a_stage_of_one_kind_has_no_layers_of_the_other():
    """Three blocks: the linear run alone. The full layers' leaves have no
    layers, and a step leaves them so."""
    model = TINY + "@3"
    pipe = decode.build_decode_pipeline(model, None, max_len=16)
    cache = pipe._fresh_caches(1)[0]
    assert cache["k"].shape == (0, 1, 16, 32)
    assert cache["gdn_state"].shape[0] == 3
    out = np.asarray(pipe.generate(np.array([[1, 2, 3, 4, 5]]), 3))
    assert out.shape == (1, 8)


def test_leaves_of_kinds_need_the_stages_runs():
    cfg = registry.get_model_config(TINY)
    with pytest.raises(ValueError, match="runs of kinds"):
        stage_cache.init_cache(cfg, 8, 1, 16,
                          leaves=qwen3_next.cache_leaves(cfg))


def _counters():
    return {(name, phase): prom.REGISTRY.counter(
        f"pipeedge_{name}_total", "").value(phase=phase)
        for name in qwen3_next.STATS for phase in ("prefill", "decode")}


def test_counters_of_one_batch_are_what_its_sizes_predict(tiny):
    _, _, pipe, ids, _ = tiny
    before = _counters()
    pipe.generate(ids[:, :21], 8)
    gained = {key: value - before[key] for key, value in _counters().items()}
    # 2 rows x 21 positions x 6 linear layers, in spans of 8, 8 and 5
    assert gained["gdn_positions_chunked", "prefill"] == 2 * 21 * 6
    assert gained["gdn_positions_stepped", "prefill"] == 0
    assert gained["gdn_state_carries", "prefill"] == 3 * 6
    assert gained["gdn_positions_chunked", "decode"] == 0
    assert gained["gdn_positions_stepped", "decode"] == 2 * 7 * 6
    assert gained["gdn_state_carries", "decode"] == 7 * 6
    assert gained["moe_layer_calls", "prefill"] == 3 * 8
    assert gained["moe_layer_calls", "decode"] == 7 * 8
    # the CPU keeps the einsums (`decoder.attend_masked`)
    assert gained["attend_fused_calls", "prefill"] == 0
    assert gained["attend_fused_calls", "decode"] == 0
    # 2 of 8 a token, 4 of 8 held: about one held assignment a token a layer
    assert 0 < gained["moe_assignments", "prefill"] <= 2 * 21 * 8 * 2


# -- what it runs, and what it refuses by name ----------------------------------

def test_a_prefix_is_a_state_and_rows_broadcast_over_the_batch(tiny):
    _, _, pipe, ids, _ = tiny
    whole = np.asarray(pipe.generate(ids[:1, :21], 6))
    handle = pipe.precompute_prefix(ids[0, :13])
    suffix = np.repeat(ids[:1, 13:21], 3, axis=0)
    got = np.asarray(pipe.generate(suffix, 6, prefix=handle))
    for row in got:
        np.testing.assert_array_equal(row[8:], whole[0, 21:])


def test_a_handle_from_a_pipeline_of_other_leaves_is_refused(tiny):
    """`_prefix_sig` stamps the named leaves' geometry: the two signatures,
    and not an error inside jit."""
    _, _, pipe, ids, _ = tiny
    other = decode.build_decode_pipeline("pipeedge/test-tiny-keye", None,
                                         max_len=32)
    handle = other.precompute_prefix(np.arange(5))
    with pytest.raises(ValueError, match="incompatible pipeline") as caught:
        pipe.generate(ids[:, :4], 2, prefix=handle)
    assert "gdn_state" in str(caught.value) and "'ik'" in str(caught.value)
    # the same family at another cut of its experts shares the geometry
    assert pipe._prefix_sig() == decode.build_decode_pipeline(
        TINY, None, max_len=32)._prefix_sig()


def test_the_dense_served_path_runs_it(tiny):
    """`tools/serve.py` without pages: the wave batcher over per-request
    caches, chunked prefill included, token for token."""
    from pipeedge_tpu.parallel.batcher import ContinuousBatcher
    _, _, pipe, ids, _ = tiny
    prompts = [ids[:1, :7], ids[1:, :13], ids[:1, 5:10]]
    batcher = ContinuousBatcher(pipe, max_active=2, chunk_tokens=4)
    for rid, prompt in enumerate(prompts):
        batcher.submit(rid, prompt, new_tokens=5)
    results = batcher.run()
    for rid, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            results[rid], np.asarray(pipe.generate(prompt, 5)))


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
        with pytest.raises(NotImplementedError, match="qwen3_next"):
            qwen3_next.FAMILY.sublayer({}, 0, None, entry.config)
        return
    if asked in ("kv_pages", "speculative"):
        pipe = decode.DecodePipeline(entry.family.FAMILY, entry.config,
                                     [(1, 32)], [params], max_len=32)
    if asked == "kv_pages":
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import serve
        with pytest.raises(NotImplementedError, match="qwen3_next"):
            serve._Service(pipe, kv_pages=4)
        return
    if asked == "speculative":
        from pipeedge_tpu.parallel.speculative import SpeculativeDecoder
        draft = decode.build_decode_pipeline("pipeedge/test-tiny-gpt2", None,
                                             max_len=32)
        for target, drafter in ((pipe, draft), (draft, pipe)):
            with pytest.raises(NotImplementedError,
                               match="qwen3_next.*earlier position"):
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
    entry = registry.get_model_entry(CELL)
    cfg = entry.config
    assert (entry.layers, cfg.num_hidden_layers, cfg.held_experts,
            cfg.n_experts, cfg.vocab_size) == (16, 4, (0, 256), 512, 75968)
    assert [qwen3_next.block_kind(cfg, i) for i in range(4)] \
        == ["linear"] * 3 + ["full"]
    assert cfg.prefill_chunk % cfg.linear_chunk == 0
    assert 31744 % cfg.prefill_chunk == 0       # the cell's prompt, in spans
    # every parameter of the cut, by the loader's shapes: 3.678 G
    stage = ShardConfig(1, 16, is_first=True, is_last=True)
    params = jax.eval_shape(lambda: qwen3_next._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert count == costs.held_parameters(_config(tiny=False)) == 3677613120


# -- the benchmark's cost functions --------------------------------------------

@pytest.mark.parametrize("size", ["tiny", "published"])
def test_costs_against_hand_counts(size):
    config = _config(tiny=size == "tiny")
    if size == "tiny":
        # channels 2 x 2 x 8 + 4 x 8 = 64; in_proj_qkvz 32 x (64 + 32),
        # in_proj_ba 32 x 8, conv 64 x 4, out 32 x 32, dt_bias, A_log 4 + 4,
        # norm 8
        assert costs.conv_channels(config) == 64
        assert costs.linear_mixer_params(config) \
            == 3072 + 256 + 256 + 1024 + 8 + 8 == 4624
        # q 32 x 128, k and v 32 x 32, o 64 x 32, two norms of 16
        assert costs.attention_params(config) \
            == 4096 + 2048 + 2048 + 32 == 8224
        assert costs.expert_params(config) == 3 * 32 * 16
        # a router of 8, the shared expert, its gate, two norms
        assert costs.layer_fixed_params(config) == 256 + 1536 + 32 + 64
        assert costs.held_parameters(config) == 6 * 4624 + 2 * 8224 \
            + 8 * (1888 + 4 * 1536) + 2 * 32 * 50 + 32
        assert costs.state_bytes_a_row(config) == 6 * 4 * (4 * 64 + 3 * 64)
        assert costs.kv_bytes_a_token(config) == 2 * 2 * 32 * 4
        assert costs.expected_held_a_token(config) == 1.0
        products = 2 * (6 * 4624 + 2 * 8224 + 8 * (1888 + 1.0 * 1536))
        assert costs.token_product_flops(config) == products
        assert costs.recurrence_flops(config) == 7 * 64
        assert costs.attention_pair_flops(config) == 4 * 4 * 16
        chunk = costs.chunk_flops(config)
        assert chunk == 6 * 4096 * 8 + 4 * 4096 * 8 + 6 * 64 * 64 \
            + 2 * 64 ** 3 // 3
        assert costs.prefill_flops(config, 2, 20) == 2 * (
            20 * products + 6 * 4 * 1 * chunk + 2 * 256 * 210 + 2 * 32 * 50)
        assert costs.decode_step_flops(config, 2, 21) == 2 * (
            products + 6 * 4 * 448 + 2 * 256 * 21 + 2 * 32 * 50)
        assert costs.decode_step_bytes(config, 2, 21, 1.5) == 2 * (
            6 * 4624 + 2 * 8224 + 8 * (1888 + 1.5 * 1536) + 32 * 50 + 32) \
            + 2 * (21 * 512 + 2 * 10752)
        return
    # ISSUE 33's arithmetic: a Gated DeltaNet mixer 33.72 M (25.17 + 0.13 +
    # 0.03 + 8.39), the gated attention 27.26 M (16.78 + 1.05 + 1.05 +
    # 8.39), a layer's router, shared expert and gate 4.20 M, an expert
    # 3.146 M; 3.678 G parameters
    assert costs.linear_mixer_params(config) == 25165824 + 131072 + 32768 \
        + 8388608 + 64 + 128 == 33718464
    assert costs.attention_params(config) == 16777216 + 2 * 1048576 \
        + 8388608 + 512 == 27263488
    assert costs.expert_params(config) == 3145728
    assert costs.layer_fixed_params(config) == 1048576 + 3145728 + 2048 \
        + 4096 == 4200448
    assert costs.held_parameters(config) == 3 * 33718464 + 27263488 \
        + 4 * (4200448 + 256 * 3145728) + 2 * 2048 * 75968 + 2048 \
        == 3677613120
    assert costs.expected_held_a_token(config) == 5.0
    # 0.42 GFLOP of products a token; a pair 16,384 FLOP; a chunk of a head
    # 11.7 MFLOP (17.6 MFLOP a token in 96 heads x layers; the issue: "about
    # 19")
    assert 4.1e8 < costs.token_product_flops(config) < 4.2e8
    assert costs.attention_pair_flops(config) == 16384
    assert costs.chunk_flops(config) == 6 * 4096 * 128 + 4 * 4096 * 128 \
        + 6 * 64 * 16384 + 2 * 64 ** 3 // 3
    assert 17e6 < 96 * costs.chunk_flops(config) / 64 < 20e6
    # a prefill of the cell 0.18 PFLOP; a step at 32k 2.7 GB with 37 of 256
    # experts touched a layer (0.94 GB of experts, 1.07 of keys and values,
    # 0.31 of head, 0.29 of other weights, 0.11 of state)
    assert 1.7e14 < costs.prefill_flops(config, 8, 31744) < 1.85e14
    assert 2.65e9 < costs.decode_step_bytes(config, 8, 32256, 37.4) < 2.8e9
    # every weight but the embedding table once (7.04 GB), the state written
    assert 7.0e9 < costs.prefill_bytes(config, 8, 31744) - 8 * 31744 * 4096 \
        < 7.2e9
