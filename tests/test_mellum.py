"""The mellum family (models/mellum.py: laguna's window-and-full block under a
second name, no gate a head, no shared expert, no dense layer) against the
benchmark's plain reference, on the CPU at `pipeedge/test-tiny-mellum`, with
seeded weights in the published key scheme; and the step whose rows stand
each at its own position (parallel/decode_rows.py) over that block: a ring a
slot, runs of two kinds of block in one stage, experts under rows that step
together, the executor's prompt pass in the family's spans."""
import dataclasses
import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_mellum as costs, weights
from benchmark.reference import mellum as reference
from pipeedge_tpu.models import (ShardConfig, decoder, laguna, mellum,
                                 registry)
from pipeedge_tpu.models.layers import rope_frequencies
from pipeedge_tpu.parallel import batcher as batcher_mod, decode, decode_rows
from pipeedge_tpu.parallel.batcher import ContinuousBatcher
from pipeedge_tpu.parallel.expert import topk_ffn_delta
from pipeedge_tpu.telemetry import metrics as prom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "mellum2-12b-a2.5b-instruct.json"
TINY = "pipeedge/test-tiny-mellum"
CELL = "JetBrains/Mellum2-12B-A2.5B-Instruct@8"
LENGTH, MAX_LEN = 44, 48    # five and a half of the tiny model's windows


def _config(tiny=True):
    with open(os.path.join(REPO, "benchmark", "configs", NAME)) as file:
        config = json.load(file)
    if tiny:
        with open(os.path.join(REPO, "tests", "benchmark_checks", "tiny",
                               "configs", NAME)) as file:
            config.update(json.load(file))
    return config


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The benchmark's tiny cut, eight blocks `sssfsssf` in one stage:
    (config, weights file, pipeline, reference), `reference(ids [S])` the
    plain forward's logits [MAX_LEN, V] of one row padded to MAX_LEN (causal:
    the padding changes nothing before it; one compiled shape)."""
    config = _config()
    path = weights.write(config, 2 ** 31 + 11, str(
        tmp_path_factory.mktemp("mellum") / "weights.npz"))
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=MAX_LEN, dtype=jnp.float32,
        model_file=path)

    def forward(ids):
        padded = np.zeros(MAX_LEN, np.int64)
        padded[:len(ids)] = ids
        with np.load(path) as tensors:
            return reference.forward(config, tensors, padded[None])[0]
    return config, path, pipe, forward


# float32 program against float32 reference: they differ by the order of
# their sums (a ring's slots against the whole sequence under a mask, a KV
# group at a time against all heads at once, the experts' tiles against
# every expert over every token; a few 1e-7 of the logits' range measured);
# 1e-5 leaves room for another BLAS and would fail a bfloat16 product or a
# slot read at the wrong position a hundred times over
# (`test_bfloat16_activations_are_outside_the_tolerance`)
TOLERANCE = 1e-5


def _gap(got, wanted):
    return np.abs(got - wanted).max() / (wanted.max() - wanted.min())


def _logits_through_the_cache(pipe, ids, prompt_len):
    """[S - prompt_len + 1, V]: the prompt in the family's spans, then a
    step a position, teacher-forced."""
    ids = np.asarray(ids)[None]
    data, caches = pipe._prefill(jnp.asarray(ids[:, :prompt_len], jnp.int32))
    assert data.shape[1] == 1       # the head saw the last row only
    got = [np.asarray(data[0, -1])]
    for pos in range(prompt_len, ids.shape[1]):
        data, caches = pipe.extend(ids[:, pos:pos + 1], caches, pos)
        got.append(np.asarray(data[0, 0]))
    return np.stack(got)


@pytest.fixture(scope="module")
def sequence(tiny):
    config, _, _, forward = tiny
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            size=LENGTH)
    return ids, forward(ids)


# spans of 4 into rings of 8: a prompt shorter than a span (3), past one
# window (13), past three and no whole number of spans (27: every ring has
# wrapped before the first step); the steps after each run to position 43
@pytest.mark.parametrize("prompt_len", [3, 13, 27])
def test_spans_then_decode_match_the_reference(prompt_len, tiny, sequence):
    _, _, pipe, _ = tiny
    ids, wanted = sequence
    got = _logits_through_the_cache(pipe, ids, prompt_len)
    assert _gap(got, wanted[prompt_len - 1:LENGTH]) <= TOLERANCE


def test_bfloat16_activations_are_outside_the_tolerance(tiny, sequence):
    """The tolerance is a float32 one: the same program over the same file
    with bfloat16 activations and cache misses it by orders of magnitude."""
    config, path, _, _ = tiny
    ids, wanted = sequence
    narrow = decode.build_decode_pipeline(
        config["program_model"], None, max_len=MAX_LEN, dtype=jnp.bfloat16,
        model_file=path)
    family = dataclasses.replace(narrow.family, **decoder.token_hooks(
        "mellum", jnp.bfloat16, laguna.rms_norm))
    narrow = decode.DecodePipeline(
        family, narrow.cfg, [(1, 4 * narrow.cfg.num_hidden_layers)],
        [narrow.stages[0]["params"]], MAX_LEN, dtype=jnp.bfloat16)
    got = _logits_through_the_cache(narrow, ids[:29], 27).astype(np.float32)
    assert _gap(got, wanted[26:29]) > 100 * TOLERANCE


@pytest.mark.parametrize("which", ["tiny", "published"])
def test_yarn_frequencies_are_the_formulas(which):
    """The full layers' frequencies, the program's and the reference's,
    against the formula written out; the published configuration's ramp
    runs from frequency 18 to 35."""
    config = _config(tiny=which == "tiny")
    cfg = registry.get_model_config(config["program_model"])
    rope = config["rope_parameters"]["full_attention"]
    dim, theta = config["head_dim"], rope["rope_theta"]

    def turns(n):
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (2 * math.pi * n)) / (2 * math.log(theta))

    low = math.floor(turns(rope["beta_fast"]))
    high = math.ceil(turns(rope["beta_slow"]))
    if which == "published":
        assert (low, high) == (18, 35)
        assert rope["attention_factor"] == 0.1 * math.log(16) + 1
    plain = theta ** (-np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    wanted = plain / rope["factor"] * ramp + plain * (1 - ramp)
    got = laguna.full_frequencies(cfg)
    theirs, factor = reference.frequencies(rope, dim)
    # float32 frequencies against float64 ones: rounding alone
    np.testing.assert_allclose(got, wanted, rtol=1e-6)
    np.testing.assert_allclose(theirs, wanted, rtol=1e-6)
    assert factor == cfg.rope_yarn[4] == rope["attention_factor"]
    # the window layers: plain, the same base, the whole head
    sliding = config["rope_parameters"]["sliding_attention"]
    assert cfg.sliding_rope_theta == sliding["rope_theta"]
    np.testing.assert_allclose(
        rope_frequencies(dim, cfg.sliding_rope_theta),
        reference.frequencies(sliding, dim)[0], rtol=1e-6)
    assert cfg.partial_rotary_factor == 1.0 and not cfg.head_gate


def test_one_block_serves_both_families_and_the_loader_counts_the_file():
    """mellum is laguna's block under another name, and the cell's cut holds
    what `costs_mellum.held_parameters` says (the configuration file's
    `deployment` quotes it)."""
    assert mellum.FAMILY.cached_block_step is laguna.cached_block_step
    assert mellum.FAMILY.rows_block_step is laguna.rows_block_step
    assert mellum.load_params is laguna.load_params
    entry = registry.get_model_entry(CELL)
    # the loader's own assembly over a `get` that makes no values
    shapes = jax.eval_shape(lambda: laguna._assemble(
        entry.config, ShardConfig(1, entry.layers, is_first=True,
                                  is_last=True),
        lambda key, shape: jnp.zeros(shape, jnp.bfloat16), jnp.bfloat16))
    held = sum(math.prod(leaf.shape)
               for leaf in jax.tree_util.tree_leaves(shapes))
    assert held == costs.held_parameters(_config(tiny=False)) \
        == 3_794_968_832
    assert entry.weights_file == "Mellum2-12B-A2.5B-Instruct@8.npz"


# -- the rows that step together ----------------------------------------------

def _rows_live():
    return batcher_mod.M_ROWS.value(kind="live")


def _counted(name, phase="decode"):
    return prom.REGISTRY.counter(f"pipeedge_{name}_total", "").value(
        phase=phase)


# a greedy token the program picked against the float32 reference's logits at
# its position: the reference's largest logit less the picked token's, over
# the row's range. 0 where both pick the same; two float32 computations
# differ only where two logits tie to 1e-6, and a ring's slot read for
# another row's position, or a row routed with a neighbour's token, moves
# the logits by tenths of their range
PICK_TOLERANCE = 1e-5


def _held_to_the_reference(forward, out, prompt_len):
    logits = forward(out)
    for position in range(prompt_len, len(out)):
        row = logits[position - 1]
        gap = (row.max() - row[out[position]]) / (row.max() - row.min())
        assert gap <= PICK_TOLERANCE, (position, gap)


def _prompt(rng, config, length):
    return rng.integers(0, config["vocab_size"], size=(1, length))


# each scenario: (slots, [(ticks before it is submitted, prompt length, new
# tokens)]). The window is 8 and a span 4, so prompts of 5, 9, 14 and 27
# leave their rings at four different phases, one not yet round, one round
# three times
SCENARIOS = {
    # four rows at four phases of their rings, stepping together to the end
    "phases": (4, [(0, 5, 12), (0, 9, 12), (0, 14, 12), (0, 27, 12)]),
    # the second of three ends early: a dead slot between two live ones
    "a_dead_slot_between": (4, [(0, 13, 14), (0, 6, 2), (0, 21, 14)]),
    # two slots; the long first owner of slot 0 ends and a short request,
    # whose ring never comes round, takes the slot: nothing of the old
    # ring (30 positions, round three times) may outlive it
    "a_slot_taken_again": (2, [(0, 30, 3), (0, 11, 16), (0, 3, 10)]),
    # a request joins while two others are mid-run
    "a_request_joins": (4, [(0, 10, 16), (0, 19, 16), (9, 7, 8)]),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_rows_step_together_as_each_alone_and_as_the_reference(scenario,
                                                               tiny):
    config, _, pipe, forward = tiny
    # two kinds of block in one stage, four runs
    assert pipe.stages[0]["runs"] == (
        ("sliding_routed", 3), ("full_routed", 1)) * 2
    assert decode_rows.rows_block_fn(pipe) is laguna.rows_block_step
    slots, plan = SCENARIOS[scenario]
    rng = np.random.default_rng(len(scenario))
    prompts = [_prompt(rng, config, length) for _, length, _ in plan]
    batcher = ContinuousBatcher(pipe, max_active=slots)
    assert batcher.rows is not None
    live = _rows_live()
    batcher.count_stats()
    assigned, calls = _counted("moe_assignments"), _counted("moe_layer_calls")
    ticks = 0
    for i, (after, _, new_tokens) in enumerate(plan):
        while ticks < after:
            batcher.tick()
            ticks += 1
        batcher.submit(i, prompts[i], new_tokens=new_tokens)
    results = batcher.run()
    for i, (_, length, new_tokens) in enumerate(plan):
        alone = np.asarray(pipe.generate(prompts[i], new_tokens))
        np.testing.assert_array_equal(results[i], alone)
        _held_to_the_reference(forward, results[i][0], length)
    # every step after a request's first token was a row of a step of all
    assert _rows_live() - live == sum(new - 1 for _, _, new in plan)
    # a dead row goes to no expert and adds to no counter: the decode-phase
    # assignments are the live rows' alone, 3 experts in each of 8 layers
    # (`generate` above counted into the same registry: taken out)
    alone_steps = sum(new - 1 for _, _, new in plan)
    batcher.count_stats()
    assert _counted("moe_assignments") - assigned \
        == 2 * alone_steps * 8 * config["num_experts_per_tok"]
    assert _counted("moe_layer_calls") - calls > 0


def test_experts_under_rows_give_each_row_what_it_gets_alone():
    """`topk_ffn_delta` over six rows, two of them dead: a live row's delta
    is what the same token gets in a call of its own (the router drops
    nothing: rows do not compete), a dead row's is zero, and the counts are
    the live rows'."""
    cfg = registry.get_model_config(TINY)
    rng = np.random.default_rng(9)
    d, e, f = cfg.hidden_size, cfg.n_experts, cfg.moe_intermediate_size
    params = {"router": {"w": jnp.asarray(rng.normal(size=(d, e)),
                                          jnp.float32)},
              "experts": {name: jnp.asarray(rng.normal(0, 0.2, size=shape),
                                            jnp.float32)
                          for name, shape in (("gate", (e, f, d)),
                                              ("up", (e, f, d)),
                                              ("down", (e, d, f)))}}
    rows = jnp.asarray(rng.normal(size=(6, 1, d)), jnp.float32)
    live = jnp.asarray([True, False, True, True, False, True])
    together = jax.jit(lambda rows, live: topk_ffn_delta(params, rows, cfg,
                                                        live=live))
    one = jax.jit(lambda row: topk_ffn_delta(params, row, cfg))
    delta, stats = together(rows, live)
    chosen = set()
    for r in range(6):
        alone, _ = one(rows[r:r + 1])
        if live[r]:
            # the same products in the same order, a row at a time: equal
            # to rounding of the float32 sums over another tile's rows
            np.testing.assert_allclose(delta[r], alone[0], rtol=1e-5,
                                       atol=1e-6)
            logits = rows[r, 0] @ params["router"]["w"]
            chosen |= set(np.argsort(-np.asarray(logits))[
                :cfg.num_experts_per_tok].tolist())
        else:
            assert not np.asarray(delta[r]).any()
    assert int(stats[0]) == 4 * cfg.num_experts_per_tok
    assert int(stats[2]) == len(chosen)


@pytest.mark.parametrize("prompt_len", [3, 4, 7])
def test_the_executors_prompt_pass_in_spans_gives_the_one_pass_logits(
        prompt_len, tiny):
    """A prompt that fits one pass (no longer than a ring): the executor's
    "chunk" waves, a span of 4 at a time on the request's own cache, end in
    the logits of the whole-prompt program's last row."""
    config, _, pipe, _ = tiny
    ids = _prompt(np.random.default_rng(prompt_len), config, prompt_len)
    stage = pipe.stages[0]
    whole, _ = stage["prefill"](stage["params"], jnp.asarray(ids, jnp.int32),
                                pipe._fresh_caches(1)[0])
    req = batcher_mod._build_request(pipe, "r", ids, 1, 0.0, 0, 0, None,
                                     None, None)
    batcher_mod._seed_caches(pipe, req)
    kind, data = batcher_mod._maybe_chunk(req, "prefill", req.ids,
                                          pipe.prefill_span, always=True)
    spans = 0
    while True:
        assert kind == "chunk" and data.shape[1] <= pipe.prefill_span
        out = batcher_mod._run_stage(pipe, 0, req, data, kind)
        spans += 1
        if req.chunk_final:
            break
        data = batcher_mod._next_chunk(req, pipe.prefill_span)
    assert spans == -(-prompt_len // pipe.prefill_span)
    assert out.shape[1] == 1        # the head saw the last row only
    assert _gap(np.asarray(out[0, 0]), np.asarray(whole[0, -1])) <= TOLERANCE


def _stub(**over):
    """What `rows_block_fn` reads of a pipeline, around a family's own
    leaves: no program is built."""
    entry = registry.get_model_entry(over.pop("model"))
    family = entry.family.FAMILY
    leaves = getattr(family, "cache_leaves", None)
    fields = dict(family=family, cfg=entry.config, cache_bits=0, mesh=None,
                  ep_mesh=None, tp_ep_mesh=None, sp_degree=1,
                  cache_leaves=leaves(entry.config) if leaves else None)
    return types.SimpleNamespace(**dict(fields, **over))


@pytest.mark.parametrize("case, over", [
    ("a whole leaf (a state a request)", dict(model="pipeedge/test-tiny-lfm2")),
    ("a whole leaf beside rows", dict(model="pipeedge/test-tiny-qwen3-next")),
    ("a strided leaf", dict(model="pipeedge/test-tiny-minicpm-sala")),
    ("an int8 cache", dict(model="pipeedge/test-tiny-gpt2", cache_bits=8)),
    ("a tp mesh", dict(model="pipeedge/test-tiny-gpt2", mesh=object())),
    ("an ep mesh", dict(model=TINY, ep_mesh=object())),
    ("sp prefill", dict(model=TINY, sp_degree=2)),
    ("capacity-bound experts", dict(model="pipeedge/test-tiny-moe")),
])
def test_rows_block_fn_still_answers_none(case, over):
    assert decode_rows.rows_block_fn(_stub(**over)) is None


@pytest.mark.parametrize("model, fn", [
    (TINY, laguna.rows_block_step),
    ("pipeedge/test-tiny-laguna", laguna.rows_block_step),
    ("pipeedge/test-tiny-gpt2", decode_rows.block_step_rows),
])
def test_rows_block_fn_answers_where_every_leaf_is_rows_or_a_ring(model, fn):
    assert decode_rows.rows_block_fn(_stub(model=model)) is fn
