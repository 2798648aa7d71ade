"""How the SPMD driver runs a stage's blocks inside a tick (parallel/spmd.py).

`tests/test_spmd.py` and `tests/test_train.py` are slow as whole modules;
this file is what the quick run holds the layer to. A four-block model at
`pipeedge/test-tiny-vit`'s widths, on conftest's virtual devices, cut
evenly, unevenly and not at all: the logits are one whole shard's, the
traced program has a `lax.cond` only where some stage is padded and no loop
below the tick scan, `min_blocks` and the gauges read the partition, and
`jax.grad` through an uneven rematerialised pipeline is the whole shard's.

And when the edge leaves a tick ahead of its use (`spmd.edge_lead`). The
tiny models reach the lead by patching the named threshold: with it the
logits are the other schedule's to the bit, in the tick no block reads that
tick's `ppermute`, and one stage or a short round trace to the program the
threshold set to "never" gives.
"""
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu.models import ShardConfig
from pipeedge_tpu.models import vit as vit_mod
from pipeedge_tpu.models.layers import TransformerConfig
from pipeedge_tpu.models.shard import make_shard_fn
from pipeedge_tpu.parallel import spmd, train

CFG = TransformerConfig(model_type="vit", hidden_size=32, num_hidden_layers=4,
                        num_attention_heads=4, intermediate_size=64,
                        num_labels=5, image_size=16, patch_size=4)
WHOLE = ShardConfig(1, 4 * CFG.num_hidden_layers, is_first=True, is_last=True)
UBATCH, SEQ = 2, 17     # 16 patches and the class token

# name -> (partition, blocks a stage)
PARTITIONS = {
    "even": ([(1, 4), (5, 8), (9, 12), (13, 16)], [1, 1, 1, 1]),
    "uneven": ([(1, 8), (9, 12), (13, 16)], [2, 1, 1]),
    "one_stage": ([(1, 16)], [4]),
    "uneven_two": ([(1, 12), (13, 16)], [3, 1]),
    "even_two": ([(1, 8), (9, 16)], [2, 2]),
}
ALWAYS, NEVER = 1.0, 0.0    # `EDGE_LEAD_SHARE`: every edge leads / none does


@pytest.fixture
def lead_share(monkeypatch):
    """Set the threshold the lead is taken under (`spmd.EDGE_LEAD_SHARE`)."""
    def patch(share):
        monkeypatch.setattr(spmd, "EDGE_LEAD_SHARE", share)
    return patch


@pytest.fixture(scope="module")
def whole_params():
    """One whole shard's parameters, every leaf moved well off its initial
    value: the seeded init's blocks are near the identity (weights of 0.02,
    zero biases), and a skipped block has to show in the logits."""
    params = vit_mod.init_params(CFG, WHOLE)
    rng = np.random.default_rng(7)
    return jax.tree_util.tree_map(
        lambda leaf: leaf + jnp.asarray(
            rng.normal(0, 0.2, size=leaf.shape), leaf.dtype), params)


def _build(whole_params, name, dp=1, tp=1, **kwargs):
    partition, per_stage = PARTITIONS[name]
    starts = np.cumsum([0] + per_stage)
    stage_params = []
    for i, (a, b) in enumerate(zip(starts[:-1], starts[1:])):
        p = {"blocks": jax.tree_util.tree_map(lambda leaf: leaf[a:b],
                                              whole_params["blocks"])}
        if i == 0:
            p["embeddings"] = whole_params["embeddings"]
        if i == len(per_stage) - 1:
            p["final"] = whole_params["final"]
        stage_params.append(p)
    mesh = spmd.make_pipeline_mesh(len(partition), dp=dp, tp=tp)
    return spmd.build_spmd_pipeline(vit_mod.FAMILY, CFG, partition,
                                    stage_params, mesh, **kwargs)


def _images(n_ubatch, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n_ubatch, UBATCH, 3, 16, 16)),
                       jnp.float32)


def _nested(eqns):
    """Every one of `eqns` and of the programs nested in them."""
    for eqn in eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _nested(sub.eqns)


def _equations(jaxpr):
    """Every equation of `jaxpr` and of the programs nested in it."""
    return _nested(jaxpr.eqns)


def _tick_scan(pipe, inputs):
    traced = jax.make_jaxpr(pipe.compiled_for(inputs))(pipe.params, inputs)
    n_ticks = pipe.n_ticks(inputs.shape[0])
    ticks = [eqn for eqn in _equations(traced.jaxpr)
             if eqn.primitive.name == "scan"
             and eqn.params["length"] == n_ticks]
    assert len(ticks) == 1, "one tick scan a program"
    return ticks[0]


def _tick_body(pipe, inputs):
    return _tick_scan(pipe, inputs).params["jaxpr"].jaxpr


@pytest.mark.parametrize("name", ["even", "uneven", "one_stage"])
def test_logits_are_one_whole_shards(whole_params, name):
    pipe = _build(whole_params, name)
    inputs = _images(6)
    got = np.asarray(pipe.run(inputs))
    whole = make_shard_fn(vit_mod.FAMILY, CFG, WHOLE)
    expected = np.stack([np.asarray(whole(whole_params, u)) for u in inputs])
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)
    # the comparison can see a block: without the last one it fails
    short = jax.tree_util.tree_map(lambda leaf: leaf[:-1],
                                   whole_params["blocks"])
    cut = dict(whole_params, blocks=short)
    assert np.max(np.abs(np.asarray(whole(cut, inputs[0])) - expected[0])) \
        > 1e-2


@pytest.mark.parametrize("name", ["even", "uneven", "one_stage"])
def test_tick_has_a_cond_only_for_padded_slots(whole_params, name):
    """Tracing only, nothing compiles. Below the tick scan there is no loop
    (the blocks are unrolled), and the conds that return the hidden state
    are the padded slots'; the last stage's `finalize` has a cond of its
    own, which returns logits."""
    pipe = _build(whole_params, name)
    body = _tick_body(pipe, _images(3))
    nested = list(_equations(body))
    loops = [eqn.primitive.name for eqn in nested
             if eqn.primitive.name in ("scan", "while")]
    assert not loops
    hidden = (UBATCH, SEQ, CFG.hidden_size)
    block_conds = [eqn for eqn in nested if eqn.primitive.name == "cond"
                   and [v.aval.shape for v in eqn.outvars] == [hidden]]
    assert len(block_conds) == pipe.max_blocks - pipe.min_blocks
    # and the slices of the stack are the tick's invariants, not its work
    slicing = [eqn.primitive.name for eqn in body.eqns
               if eqn.primitive.name in ("slice", "dynamic_slice", "gather")
               and any(v.aval.shape[-2:] == (CFG.hidden_size,
                                             CFG.intermediate_size)
                       for v in eqn.outvars)]
    assert not slicing


@pytest.mark.parametrize("name", ["even", "uneven", "one_stage"])
def test_min_blocks_and_gauges_read_the_partition(whole_params, name):
    per_stage = PARTITIONS[name][1]
    pipe = _build(whole_params, name)
    assert (pipe.min_blocks, pipe.max_blocks) \
        == (min(per_stage), max(per_stage))
    assert spmd._M_STAGE_BLOCKS.value(kind="unconditional") == min(per_stage)
    assert spmd._M_STAGE_BLOCKS.value(kind="masked") \
        == max(per_stage) - min(per_stage)


# -- the edge a tick ahead of its use (`spmd.edge_lead`) ----------------------

@pytest.mark.parametrize("n_ubatch, n_stages, lead", [
    (1024, 4, 1),     # `vit-l.spmd-4stage`: 3 ticks of 1,030 against 2.5%
    (128, 4, 1),      # its traced round
    (96, 4, 0),       # four stages break even between these two on the chip
    (4, 4, 0),        # `tools/train.py -u 4`: 10 ticks for 7 would cost 43%
    (1, 2, 0),
    (1024, 1, 0), (1, 1, 0),    # one stage has no edge
    (1024, 2, 1), (1024, 8, 1), (512, 8, 1), (256, 8, 0),
])
def test_the_lead_is_read_off_the_calls_shapes(n_ubatch, n_stages, lead):
    assert spmd.edge_lead(n_ubatch, n_stages) == lead
    pipe = spmd.SpmdPipeline(family=None, cfg=None, mesh=None,
                             n_stages=n_stages, max_blocks=1, min_blocks=1,
                             params={})
    assert pipe.n_ticks(n_ubatch) \
        == n_ubatch + (1 + lead) * (n_stages - 1)


# name -> (partition, microbatches, build options)
LEAD_CASES = {
    "two_stages": ("even_two", 6, {}),
    "four_stages": ("even", 6, {}),
    "uneven": ("uneven", 5, {}),
    "one_microbatch": ("even", 1, {}),
    "fewer_than_stages": ("even", 2, {}),
    "many": ("even", 19, {}),
    "uniform_8bit": ("even", 6, {"quant_bit": 8}),
    "mixed_edges": ("even", 6, {"quant_bit": [8, 0, 4, 0]}),
    "mixed_uneven": ("uneven", 4, {"quant_bit": [4, 8, 0]}),
    "dp_mesh": ("even", 6, {"dp": 2}),
    "dp_8bit": ("even_two", 3, {"dp": 2, "quant_bit": 8}),
    "tp_mesh": ("even_two", 4, {"tp": 2}),
    "dp_tp_mixed": ("even_two", 3, {"dp": 2, "tp": 2, "quant_bit": [4, 0]}),
}


@pytest.mark.parametrize("case", sorted(LEAD_CASES))
def test_with_the_lead_logits_are_the_other_schedules_to_the_bit(
        whole_params, lead_share, case):
    """Every microbatch goes through the same blocks on the same weights in
    the same order; only the tick it does so in moves."""
    name, n_ubatch, options = LEAD_CASES[case]
    pipe = _build(whole_params, name, **options)
    inputs = _images(n_ubatch, seed=3)
    lead_share(NEVER)
    assert spmd.edge_lead(n_ubatch, pipe.n_stages) == 0
    want = np.asarray(pipe.run(inputs))
    lead_share(ALWAYS)
    assert spmd.edge_lead(n_ubatch, pipe.n_stages) == 1
    got = np.asarray(pipe.run(inputs))
    assert len(pipe._compiled) == 2     # the lead is in the program's key
    np.testing.assert_array_equal(got, want)
    # every microbatch has its own logits: an index off by one would show
    assert len({row.tobytes() for row in got}) == n_ubatch
    assert spmd._M_EDGE_LEAD.value() == 1
    assert spmd._M_TICKS.value() \
        == n_ubatch + 2 * (pipe.n_stages - 1) == pipe.n_ticks(n_ubatch)


def _reached_from_ppermute(body):
    """(the `ppermute` equations of a tick, the tick's equations that read
    what they return, directly or through another equation)."""
    permutes = [eqn for eqn in body.eqns if eqn.primitive.name == "ppermute"]
    tainted = {v for eqn in permutes for v in eqn.outvars}
    reached = []
    for eqn in body.eqns:
        if eqn in permutes:
            continue
        if any(isinstance(v, jax.extend.core.Var) and v in tainted
               for v in eqn.invars):
            tainted.update(eqn.outvars)
            reached.append(eqn)
    return permutes, reached


def _has_matmul(eqns):
    return any(eqn.primitive.name == "dot_general" for eqn in _nested(eqns))


@pytest.mark.parametrize("options", [{}, {"quant_bit": 8},
                                     {"quant_bit": [8, 0, 4, 0]}],
                         ids=["raw", "uniform_8bit", "mixed_edges"])
def test_with_the_lead_no_block_of_a_tick_reads_that_ticks_permute(
        whole_params, lead_share, options):
    """Tracing only. What a later refactor must not undo: inside one tick
    the `ppermute` sends a carry input and nothing reads what it returns
    but the carry, so the compiler has the stage's blocks to run between
    `collective-permute-start` and `-done`. Without the lead the blocks'
    matmuls hang on it, and the same walk finds them."""
    pipe = _build(whole_params, "even", **options)
    inputs = _images(6)
    lead_share(ALWAYS)
    scan = _tick_scan(pipe, inputs)
    body = scan.params["jaxpr"].jaxpr
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    carry_in = body.invars[n_consts:n_consts + n_carry]
    carry_out = body.outvars[:n_carry]
    permutes, reached = _reached_from_ppermute(body)
    n_payload = len(permutes)
    assert n_payload == (3 if isinstance(options.get("quant_bit"), list)
                         else 1 + 2 * bool(options))
    assert n_carry == 2 * n_payload + 1     # in flight, landed, outputs
    for eqn in permutes:
        assert all(v in carry_in for v in eqn.invars)
        assert all(v in carry_out for v in eqn.outvars)
    assert not reached
    assert _has_matmul(body.eqns)       # the blocks are in this tick

    lead_share(NEVER)
    scan = _tick_scan(pipe, inputs)
    assert scan.params["num_carry"] == n_payload + 1
    _, reached = _reached_from_ppermute(scan.params["jaxpr"].jaxpr)
    assert _has_matmul(reached)


@pytest.mark.parametrize("name, n_ubatch, share", [
    ("one_stage", 6, ALWAYS),       # no edge to lead
    ("even", 6, None),              # a short round under the real threshold
    ("uneven", 3, None),
    ("even_two", 1, None),
])
def test_without_the_lead_the_program_is_the_tick_that_waits(
        whole_params, lead_share, name, n_ubatch, share):
    """`lead = 0` is not a second path: it traces to what the threshold set
    to "never" traces to, equation for equation, with the schedule of
    `n_ubatch + n_stages - 1` ticks and one payload in the carry."""
    pipe = _build(whole_params, name)
    inputs = _images(n_ubatch)
    if share is not None:
        lead_share(share)
    assert spmd.edge_lead(n_ubatch, pipe.n_stages) == 0
    assert pipe.n_ticks(n_ubatch) == n_ubatch + pipe.n_stages - 1
    scan = _tick_scan(pipe, inputs)
    assert scan.params["num_carry"] == 2    # the payload and the outputs
    got = str(jax.make_jaxpr(pipe.compiled_for(inputs))(pipe.params, inputs))
    assert spmd._M_EDGE_LEAD.value() == 0
    assert spmd._M_TICKS.value() == n_ubatch + pipe.n_stages - 1
    lead_share(NEVER)
    pipe._compiled.clear()
    want = str(jax.make_jaxpr(pipe.compiled_for(inputs))(pipe.params, inputs))
    assert got == want


@pytest.mark.parametrize("share", [NEVER, ALWAYS], ids=["waits", "leads"])
def test_grads_through_uneven_remat_pipeline_are_the_whole_shards(
        whole_params, lead_share, share):
    """The training path shares `_build`: 3 + 1 blocks and `remat=True`
    put one unconditional checkpointed slot and two masked ones under
    `jax.grad`."""
    lead_share(share)
    pipe = _build(whole_params, "uneven_two", remat=True)
    assert (pipe.min_blocks, pipe.max_blocks) == (1, 3)
    assert spmd.edge_lead(3, pipe.n_stages) == (share == ALWAYS)
    x = _images(3, seed=1)
    y = jnp.asarray(np.random.default_rng(2).integers(0, 5, (3, UBATCH)),
                    jnp.int32)
    fwd = pipe.compiled_for(x)
    n_blocks = pipe.params["n_blocks"]

    def pipe_loss(trainable):
        return train.softmax_xent(
            fwd({**trainable, "n_blocks": n_blocks}, x), y)

    trainable = {k: v for k, v in pipe.params.items() if k != "n_blocks"}
    got_loss, got = jax.value_and_grad(pipe_loss)(trainable)

    whole = make_shard_fn(vit_mod.FAMILY, CFG, WHOLE)

    def whole_loss(params):
        return train.softmax_xent(jnp.stack([whole(params, u) for u in x]), y)

    want_loss, want = jax.value_and_grad(whole_loss)(whole_params)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)

    def same(got_leaf, want_leaf):
        np.testing.assert_allclose(np.asarray(got_leaf),
                                   np.asarray(want_leaf),
                                   rtol=1e-5, atol=1e-5)

    jax.tree_util.tree_map(same, got["embed"], want["embeddings"])
    jax.tree_util.tree_map(same, got["final"], want["final"])
    # [n_stages, max_b, ...] against the whole shard's [4, ...]: stage 0
    # holds blocks 0-2, stage 1 block 3 and two padded slots (no gradient)
    for leaf, ref in zip(jax.tree_util.tree_leaves(got["blocks"]),
                         jax.tree_util.tree_leaves(want["blocks"])):
        leaf = np.asarray(leaf)
        same(leaf[0], ref[:3])
        same(leaf[1, 0], ref[3])
        assert not leaf[1, 1:].any()
