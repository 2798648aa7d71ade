"""SPMD (shard_map + ppermute) pipeline tests on the 8-device CPU mesh.

This exercises the true multi-chip path: stage-sharded parameters, ppermute
inter-stage edges, masked uneven stages, dp x stage meshes, quantized edges.
"""
import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pipeedge_tpu.models import ShardConfig  # noqa: E402
from pipeedge_tpu.models import bert as bert_mod  # noqa: E402
from pipeedge_tpu.models import gpt2 as gpt2_mod  # noqa: E402
from pipeedge_tpu.models import vit as vit_mod  # noqa: E402
from pipeedge_tpu.models.layers import TransformerConfig  # noqa: E402
from pipeedge_tpu.models.shard import make_shard_fn  # noqa: E402
from pipeedge_tpu.parallel import spmd  # noqa: E402

pytestmark = pytest.mark.slow  # every test compiles multi-stage shard_map programs

TINY4 = dict(hidden_size=32, num_hidden_layers=4, num_attention_heads=4,
             intermediate_size=64)


@pytest.fixture(scope="module")
def tiny_vit4():
    from transformers import ViTConfig, ViTForImageClassification
    hf_cfg = ViTConfig(**TINY4, image_size=16, patch_size=4, num_labels=5)
    torch.manual_seed(0)
    model = ViTForImageClassification(hf_cfg).eval()
    cfg = TransformerConfig(model_type="vit", **TINY4, num_labels=5,
                            image_size=16, patch_size=4)
    weights = vit_mod.hf_to_npz_weights(model.state_dict(), cfg)
    return cfg, weights


def _stage_params(family, cfg, partition, weights):
    total = 4 * cfg.num_hidden_layers
    out = []
    for l, r in partition:
        sc = ShardConfig(l, r, is_first=l == 1, is_last=r == total)
        out.append(family.load_params(cfg, sc, weights))
    return out


def _expected(family, cfg, weights, inputs):
    total = 4 * cfg.num_hidden_layers
    sc = ShardConfig(1, total, is_first=True, is_last=True)
    params = family.load_params(cfg, sc, weights)
    fn = make_shard_fn(family.FAMILY, cfg, sc)
    return np.stack([np.asarray(fn(params, u)) for u in inputs])


def test_partition_to_blocks_validates():
    assert spmd.partition_to_blocks([(1, 8), (9, 16)]) == [(0, 1), (2, 3)]
    with pytest.raises(ValueError):
        spmd.partition_to_blocks([(1, 6), (7, 16)])


@pytest.mark.parametrize("partition", [
    [(1, 4), (5, 8), (9, 12), (13, 16)],   # even 4-stage
    [(1, 8), (9, 12), (13, 16)],           # uneven: 2+1+1 blocks (masking)
    [(1, 16)],                             # single stage degenerate
])
def test_spmd_matches_single_shard(tiny_vit4, partition):
    cfg, weights = tiny_vit4
    mesh = spmd.make_pipeline_mesh(len(partition))
    pipe = spmd.build_spmd_pipeline(
        vit_mod.FAMILY, cfg, partition,
        _stage_params(vit_mod, cfg, partition, weights), mesh)
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.normal(size=(6, 2, 3, 16, 16)).astype(np.float32))
    got = np.asarray(pipe.run(inputs))
    expected = _expected(vit_mod, cfg, weights, inputs)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)


def test_spmd_dp_stage_mesh(tiny_vit4):
    cfg, weights = tiny_vit4
    partition = [(1, 4), (5, 8), (9, 12), (13, 16)]
    mesh = spmd.make_pipeline_mesh(4, dp=2)
    assert mesh.shape == {"dp": 2, "stage": 4}
    pipe = spmd.build_spmd_pipeline(
        vit_mod.FAMILY, cfg, partition,
        _stage_params(vit_mod, cfg, partition, weights), mesh)
    rng = np.random.default_rng(1)
    inputs = jnp.asarray(rng.normal(size=(5, 4, 3, 16, 16)).astype(np.float32))
    got = np.asarray(pipe.run(inputs))
    expected = _expected(vit_mod, cfg, weights, inputs)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)


def test_spmd_quantized_edges(tiny_vit4):
    cfg, weights = tiny_vit4
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2)
    sp = _stage_params(vit_mod, cfg, partition, weights)
    pipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, partition, sp, mesh)
    rng = np.random.default_rng(2)
    inputs = jnp.asarray(rng.normal(size=(3, 2, 3, 16, 16)).astype(np.float32))
    exact = np.asarray(pipe.run(inputs))
    pipe.stage_bits = (8, 0)
    assert pipe.quant_bit == 8
    q8 = np.asarray(pipe.run(inputs))
    err = np.max(np.abs(q8 - exact))
    assert err < np.max(np.abs(exact)) * 0.5
    assert not np.allclose(q8, exact)  # quantization actually happened


def test_spmd_per_stage_quant_bits(tiny_vit4):
    """Mixed per-stage edge bitwidths (reference -q list semantics): the
    lax.switch wire codec must agree with the exact pipeline within the
    coarsest edge's quantization error, and differ from it (quantization
    really ran). Includes a raw (bit=0) edge mixed with quantized ones."""
    cfg, weights = tiny_vit4
    partition = [(1, 4), (5, 8), (9, 12), (13, 16)]
    mesh = spmd.make_pipeline_mesh(4)
    sp = _stage_params(vit_mod, cfg, partition, weights)
    exact_pipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, partition, sp,
                                          mesh)
    rng = np.random.default_rng(5)
    inputs = jnp.asarray(rng.normal(size=(5, 2, 3, 16, 16)).astype(np.float32))
    exact = np.asarray(exact_pipe.run(inputs))

    pipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, partition, sp, mesh,
                                    quant_bit=[8, 4, 0, 0])
    assert pipe.stage_bits == (8, 4, 0, 0)
    mixed = np.asarray(pipe.run(inputs))
    assert mixed.shape == exact.shape
    assert not np.allclose(mixed, exact)       # 4-bit edge really quantized
    # 16-level edge dominates the error; outputs stay in the same regime
    assert np.max(np.abs(mixed - exact)) < np.max(np.abs(exact))

    # high-precision mixed edges track the exact result closely
    pipe16 = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, partition, sp,
                                      mesh, quant_bit=[16, 0, 16, 0])
    m16 = np.asarray(pipe16.run(inputs))
    np.testing.assert_allclose(m16, exact, rtol=0.05, atol=0.05)


def test_spmd_stage_ranks_mesh(tiny_vit4):
    """-r rank order: stages placed on the listed devices, same results."""
    cfg, weights = tiny_vit4
    partition = [(1, 8), (9, 16)]
    ranks = [3, 1]
    mesh = spmd.make_pipeline_mesh(2, stage_ranks=ranks)
    devs = list(mesh.devices.flat)
    assert devs == [jax.devices()[3], jax.devices()[1]]
    sp = _stage_params(vit_mod, cfg, partition, weights)
    pipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, partition, sp, mesh)
    rng = np.random.default_rng(6)
    inputs = jnp.asarray(rng.normal(size=(4, 2, 3, 16, 16)).astype(np.float32))
    got = np.asarray(pipe.run(inputs))
    expected = _expected(vit_mod, cfg, weights, inputs)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError):
        spmd.make_pipeline_mesh(2, stage_ranks=[1, 1])
    with pytest.raises(ValueError):
        spmd.make_pipeline_mesh(2, dp=2, stage_ranks=[0, 1])


def test_spmd_bert(tiny_vit4):
    from transformers import BertConfig, BertForSequenceClassification
    hf_cfg = BertConfig(**TINY4, vocab_size=100, max_position_embeddings=64,
                        num_labels=3)
    torch.manual_seed(3)
    model = BertForSequenceClassification(hf_cfg).eval()
    cfg = TransformerConfig(model_type="bert", **TINY4, num_labels=3,
                            vocab_size=100, max_position_embeddings=64)
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2)
    pipe = spmd.build_spmd_pipeline(
        bert_mod.FAMILY, cfg, partition,
        _stage_params(bert_mod, cfg, partition, weights), mesh)
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 100, size=(4, 2, 9)),
                      dtype=jnp.int32)
    got = np.asarray(pipe.run(ids))
    expected = _expected(bert_mod, cfg, weights, ids)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)


def test_spmd_dp_stage_tp_mesh(tiny_vit4):
    """pp x dp x tp in ONE compiled program: blocks stage-sharded AND
    Megatron tp-sharded (two psums per block over 'tp'), batch dp-sharded,
    quantized ppermute stage edges — against the single-shard oracle."""
    cfg, weights = tiny_vit4
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2, dp=2, tp=2)
    assert mesh.shape == {"dp": 2, "stage": 2, "tp": 2}
    pipe = spmd.build_spmd_pipeline(
        vit_mod.FAMILY, cfg, partition,
        _stage_params(vit_mod, cfg, partition, weights), mesh, quant_bit=8)
    rng = np.random.default_rng(6)
    inputs = jnp.asarray(rng.normal(size=(4, 4, 3, 16, 16)).astype(np.float32))
    got = np.asarray(pipe.run(inputs))
    expected = _expected(vit_mod, cfg, weights, inputs)
    # 8-bit edge quantization dominates the tolerance
    np.testing.assert_allclose(got, expected, rtol=0.1, atol=0.05)
    pipe_raw = spmd.build_spmd_pipeline(
        vit_mod.FAMILY, cfg, partition,
        _stage_params(vit_mod, cfg, partition, weights), mesh)
    got_raw = np.asarray(pipe_raw.run(inputs))
    np.testing.assert_allclose(got_raw, expected, rtol=2e-4, atol=2e-5)


def test_spmd_bert_tp(tiny_vit4):
    from transformers import BertConfig, BertForSequenceClassification
    hf_cfg = BertConfig(**TINY4, vocab_size=100, max_position_embeddings=64,
                        num_labels=3)
    torch.manual_seed(3)
    model = BertForSequenceClassification(hf_cfg).eval()
    cfg = TransformerConfig(model_type="bert", **TINY4, num_labels=3,
                            vocab_size=100, max_position_embeddings=64)
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2, dp=2, tp=2)
    pipe = spmd.build_spmd_pipeline(
        bert_mod.FAMILY, cfg, partition,
        _stage_params(bert_mod, cfg, partition, weights), mesh)
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 100, size=(4, 2, 9)),
                      dtype=jnp.int32)
    got = np.asarray(pipe.run(ids))
    expected = _expected(bert_mod, cfg, weights, ids)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)


def test_spmd_dp_stage_sp_mesh(tiny_vit4):
    """pp x dp x sp in ONE compiled program: activations sequence-sharded
    within each stage (stage edges carry only the local chunk), exact ring
    attention over 'sp' per block, last stage all-gathers for the pooler —
    against the single-shard oracle."""
    from transformers import BertConfig, BertForSequenceClassification
    hf_cfg = BertConfig(**TINY4, vocab_size=100, max_position_embeddings=64,
                        num_labels=3)
    torch.manual_seed(3)
    model = BertForSequenceClassification(hf_cfg).eval()
    cfg = TransformerConfig(model_type="bert", **TINY4, num_labels=3,
                            vocab_size=100, max_position_embeddings=64)
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2, dp=2, sp=2)
    assert mesh.shape == {"dp": 2, "stage": 2, "sp": 2}
    pipe = spmd.build_spmd_pipeline(
        bert_mod.FAMILY, cfg, partition,
        _stage_params(bert_mod, cfg, partition, weights), mesh)
    # S=12 divides sp=2
    ids = jnp.asarray(
        np.random.default_rng(7).integers(0, 100, size=(4, 4, 12)),
        dtype=jnp.int32)
    got = np.asarray(pipe.run(ids))
    expected = _expected(bert_mod, cfg, weights, ids)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("axis", ["tp", "sp"])
def test_spmd_edge_lead_on_the_inner_axes(monkeypatch, axis):
    """`spmd.edge_lead` under a within-stage axis: Megatron blocks, and the
    sp body, which embeds a microbatch a tick on stage 0 and gathers the
    chunks on the last stage. With the edge a tick ahead of its use the
    logits are the waiting schedule's to the bit, and the oracle's."""
    from transformers import BertConfig, BertForSequenceClassification
    hf_cfg = BertConfig(**TINY4, vocab_size=100, max_position_embeddings=64,
                        num_labels=3)
    torch.manual_seed(3)
    model = BertForSequenceClassification(hf_cfg).eval()
    cfg = TransformerConfig(model_type="bert", **TINY4, num_labels=3,
                            vocab_size=100, max_position_embeddings=64)
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2, dp=2, **{axis: 2})
    pipe = spmd.build_spmd_pipeline(
        bert_mod.FAMILY, cfg, partition,
        _stage_params(bert_mod, cfg, partition, weights), mesh)
    ids = jnp.asarray(
        np.random.default_rng(7).integers(0, 100, size=(5, 4, 12)),
        dtype=jnp.int32)
    got = {}
    for share in (0.0, 1.0):
        monkeypatch.setattr(spmd, "EDGE_LEAD_SHARE", share)
        assert spmd.edge_lead(5, pipe.n_stages) == int(share)
        got[share] = np.asarray(pipe.run(ids))
    np.testing.assert_array_equal(got[1.0], got[0.0])
    np.testing.assert_allclose(got[1.0], _expected(bert_mod, cfg, weights, ids),
                               rtol=2e-4, atol=2e-5)


def _tiny_gpt2():
    from transformers import GPT2Config, GPT2LMHeadModel
    hf_cfg = GPT2Config(n_embd=32, n_layer=4, n_head=4, n_inner=64,
                        vocab_size=100, n_positions=64)
    torch.manual_seed(5)
    model = GPT2LMHeadModel(hf_cfg).eval()
    cfg = TransformerConfig(model_type="gpt2", **TINY4, layer_norm_eps=1e-5,
                            vocab_size=100, max_position_embeddings=64)
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    return cfg, weights


def test_spmd_gpt2_sp_causal_ring():
    """Causal decoder through the pp x dp x sp program: sequence-sharded
    stages with CAUSAL ring attention (the long-context decode shape), last
    stage all-gathers for the full-sequence LM head — vs the single-shard
    oracle (itself HF-parity-tested in test_models.py)."""
    cfg, weights = _tiny_gpt2()
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2, dp=2, sp=2)
    pipe = spmd.build_spmd_pipeline(
        gpt2_mod.FAMILY, cfg, partition,
        _stage_params(gpt2_mod, cfg, partition, weights), mesh)
    ids = jnp.asarray(
        np.random.default_rng(11).integers(0, 100, size=(3, 4, 12)),
        dtype=jnp.int32)
    got = np.asarray(pipe.run(ids))
    expected = _expected(gpt2_mod, cfg, weights, ids)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)


def test_spmd_gpt2_tp():
    """Causal decoder through pp x tp: Megatron-sharded blocks reuse the
    ViT spec table (same param names) with the causal+gelu_new body."""
    cfg, weights = _tiny_gpt2()
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2, dp=2, tp=2)
    pipe = spmd.build_spmd_pipeline(
        gpt2_mod.FAMILY, cfg, partition,
        _stage_params(gpt2_mod, cfg, partition, weights), mesh)
    ids = jnp.asarray(
        np.random.default_rng(12).integers(0, 100, size=(4, 2, 9)),
        dtype=jnp.int32)
    got = np.asarray(pipe.run(ids))
    expected = _expected(gpt2_mod, cfg, weights, ids)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)


def test_spmd_sp_seq_divisibility_error(tiny_vit4):
    cfg, weights = tiny_vit4  # ViT: S = 17 tokens, indivisible by 2
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2, sp=2)
    pipe = spmd.build_spmd_pipeline(
        vit_mod.FAMILY, cfg, partition,
        _stage_params(vit_mod, cfg, partition, weights), mesh)
    inputs = jnp.asarray(np.zeros((3, 2, 3, 16, 16), np.float32))
    with pytest.raises(ValueError, match="sequence length 17"):
        pipe.run(inputs)


def test_spmd_sp_ulysses_matches_oracle():
    from transformers import BertConfig, BertForSequenceClassification
    hf_cfg = BertConfig(**TINY4, vocab_size=100, max_position_embeddings=64,
                        num_labels=3)
    torch.manual_seed(3)
    model = BertForSequenceClassification(hf_cfg).eval()
    cfg = TransformerConfig(model_type="bert", **TINY4, num_labels=3,
                            vocab_size=100, max_position_embeddings=64)
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    partition = [(1, 8), (9, 16)]
    mesh = spmd.make_pipeline_mesh(2, dp=2, sp=2)
    pipe = spmd.build_spmd_pipeline(
        bert_mod.FAMILY, cfg, partition,
        _stage_params(bert_mod, cfg, partition, weights), mesh,
        sp_kind="ulysses")
    ids = jnp.asarray(
        np.random.default_rng(9).integers(0, 100, size=(3, 4, 12)),
        dtype=jnp.int32)
    got = np.asarray(pipe.run(ids))
    expected = _expected(bert_mod, cfg, weights, ids)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)
