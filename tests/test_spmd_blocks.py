"""How the SPMD driver runs a stage's blocks inside a tick (parallel/spmd.py).

`tests/test_spmd.py` and `tests/test_train.py` are slow as whole modules;
this file is what the quick run holds the layer to. A four-block model at
`pipeedge/test-tiny-vit`'s widths, on conftest's virtual devices, cut
evenly, unevenly and not at all: the logits are one whole shard's, the
traced program has a `lax.cond` only where some stage is padded and no loop
below the tick scan, `min_blocks` and the gauges read the partition, and
`jax.grad` through an uneven rematerialised pipeline is the whole shard's.
"""
import jax
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
}


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


def _build(whole_params, name, **kwargs):
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
    mesh = spmd.make_pipeline_mesh(len(partition))
    return spmd.build_spmd_pipeline(vit_mod.FAMILY, CFG, partition,
                                    stage_params, mesh, **kwargs)


def _images(n_ubatch, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n_ubatch, UBATCH, 3, 16, 16)),
                       jnp.float32)


def _equations(jaxpr):
    """Every equation of `jaxpr` and of the programs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _tick_body(pipe, inputs):
    traced = jax.make_jaxpr(pipe.compiled_for(inputs))(pipe.params, inputs)
    n_ticks = inputs.shape[0] + pipe.n_stages - 1
    ticks = [eqn for eqn in _equations(traced.jaxpr)
             if eqn.primitive.name == "scan"
             and eqn.params["length"] == n_ticks]
    assert len(ticks) == 1, "one tick scan a program"
    return ticks[0].params["jaxpr"].jaxpr


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


def test_grads_through_uneven_remat_pipeline_are_the_whole_shards(
        whole_params):
    """The training path shares `_build`: 3 + 1 blocks and `remat=True`
    put one unconditional checkpointed slot and two masked ones under
    `jax.grad`."""
    pipe = _build(whole_params, "uneven_two", remat=True)
    assert (pipe.min_blocks, pipe.max_blocks) == (1, 3)
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
