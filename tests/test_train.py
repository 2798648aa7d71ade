"""Pipeline-parallel training over the SPMD pipeline (parallel/train.py).

The claim under test: jax.grad through the ONE-program pipelined forward
(ppermute edges, fill/drain masking, stage-sharded blocks) produces the
same gradients as a plain single-device forward of the same model — and
an optimizer loop on the pipeline actually learns.
"""
import json

import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pipeedge_tpu.models import ShardConfig  # noqa: E402
from pipeedge_tpu.models import vit as vit_mod  # noqa: E402
from pipeedge_tpu.models.layers import TransformerConfig  # noqa: E402
from pipeedge_tpu.models.shard import make_shard_fn  # noqa: E402
from pipeedge_tpu.parallel import spmd, train  # noqa: E402

pytestmark = pytest.mark.slow   # compiles forward+backward shard_map programs

TINY4 = dict(hidden_size=32, num_hidden_layers=4, num_attention_heads=4,
             intermediate_size=64)
PARTITION = [(1, 8), (9, 16)]


def _stage_params(cfg, weights):
    total = 4 * cfg.num_hidden_layers
    return [vit_mod.load_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == total), weights)
        for l, r in PARTITION]


@pytest.fixture(scope="module")
def setup():
    from jax.sharding import Mesh
    from transformers import ViTConfig, ViTForImageClassification
    hf_cfg = ViTConfig(**TINY4, image_size=16, patch_size=4, num_labels=5)
    torch.manual_seed(0)
    model = ViTForImageClassification(hf_cfg).eval()
    cfg = TransformerConfig(model_type="vit", **TINY4, num_labels=5,
                            image_size=16, patch_size=4)
    weights = vit_mod.hf_to_npz_weights(model.state_dict(), cfg)
    stage_params = _stage_params(cfg, weights)
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("stage",))
    pipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, PARTITION,
                                    stage_params, mesh)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 2, 3, 16, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 5, size=(3, 2)), jnp.int32)
    return cfg, weights, pipe, x, y


def _single_device_loss(cfg, weights):
    """The same model as ONE unsharded forward (oracle for grads)."""
    total = 4 * cfg.num_hidden_layers
    sc = ShardConfig(1, total, is_first=True, is_last=True)
    params = vit_mod.load_params(cfg, sc, weights)
    fn = make_shard_fn(vit_mod.FAMILY, cfg, sc)

    def loss(params, x, y):
        logits = jnp.stack([fn(params, u) for u in x])
        return train.softmax_xent(logits, y)

    return params, loss


def test_pipeline_grads_match_single_device(setup):
    """d loss/d params through the 2-stage pipelined program equals the
    single-device gradient of the same model (the ppermute/psum/scan
    transposes are exact)."""
    cfg, weights, pipe, x, y = setup
    fwd = pipe.compiled_for(x)
    n_blocks = pipe.params["n_blocks"]

    def pipe_loss(trainable):
        return train.softmax_xent(
            fwd({**trainable, "n_blocks": n_blocks}, x), y)

    trainable = {k: v for k, v in pipe.params.items() if k != "n_blocks"}
    pipe_val, pipe_grads = jax.value_and_grad(pipe_loss)(trainable)

    ref_params, ref_loss = _single_device_loss(cfg, weights)
    ref_val, ref_grads = jax.value_and_grad(ref_loss)(ref_params, x, y)
    np.testing.assert_allclose(float(pipe_val), float(ref_val),
                               rtol=1e-5, atol=1e-6)

    # EVERY leaf. Stage-stacked block grads [n_stages, max_b, ...] map to
    # the oracle's [total_blocks, ...] stack: stage s covers [2s, 2s+2)
    def path_str(kp):
        return jax.tree_util.keystr(kp)

    checked = [0]

    def check_block_leaf(kp, g, w):
        g, w = np.asarray(g), np.asarray(w)
        for s in range(2):
            np.testing.assert_allclose(
                g[s], w[2 * s:2 * s + 2], rtol=2e-4, atol=1e-5,
                err_msg=f"blocks{path_str(kp)} stage {s}")
        checked[0] += 1

    jax.tree_util.tree_map_with_path(check_block_leaf,
                                     pipe_grads["blocks"],
                                     ref_grads["blocks"])

    def check_leaf(kp, g, w, name):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=1e-5,
                                   err_msg=f"{name}{path_str(kp)}")
        checked[0] += 1

    jax.tree_util.tree_map_with_path(
        lambda kp, g, w: check_leaf(kp, g, w, "embed"),
        pipe_grads["embed"], ref_grads["embeddings"])
    jax.tree_util.tree_map_with_path(
        lambda kp, g, w: check_leaf(kp, g, w, "final"),
        pipe_grads["final"], ref_grads["final"])
    assert checked[0] > 20, f"only {checked[0]} grad leaves compared"

    # remat (per-block jax.checkpoint) recomputes instead of saving —
    # gradients must be identical
    rpipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, PARTITION,
                                     _stage_params(cfg, weights),
                                     pipe.mesh, remat=True)
    rfwd = rpipe.compiled_for(x)

    def rloss(trainable):
        return train.softmax_xent(
            rfwd({**trainable, "n_blocks": n_blocks}, x), y)

    rgrads = jax.grad(rloss)(trainable)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7),
        rgrads, pipe_grads)


@pytest.mark.parametrize("remat", [False, True], ids=["saved", "remat"])
def test_step_is_the_same_on_both_sides_of_the_edges_lead(
        setup, monkeypatch, remat):
    """`spmd.edge_lead`: with the edge a tick ahead of its use a training
    step's loss and updated parameters are the waiting schedule's (the
    same blocks on the same microbatches in the same order; the added
    bubble ticks carry zero cotangents), and the backward's transposed
    permutes have the same room as the forward's."""
    import optax
    cfg, weights, pipe, x, y = setup
    results = {}
    for share in (0.0, 1.0):
        monkeypatch.setattr(spmd, "EDGE_LEAD_SHARE", share)
        fresh = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, PARTITION,
                                         _stage_params(cfg, weights),
                                         pipe.mesh, remat=remat)
        assert spmd.edge_lead(x.shape[0], fresh.n_stages) == int(share)
        assert fresh.n_ticks(x.shape[0]) == x.shape[0] + 1 + int(share)
        step, opt_state = train.make_train_step(fresh, optax.sgd(0.05), x)
        params, _, loss = step(fresh.params, opt_state, x, y)
        results[share] = (float(loss), jax.tree_util.tree_map(np.asarray,
                                                              params))
    assert results[0.0][0] == results[1.0][0]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8),
        results[0.0][1], results[1.0][1])


def test_train_step_learns_and_shards(setup):
    """A few SGD steps through the pipeline reduce the loss; quantized
    edges are refused."""
    import optax
    cfg, weights, pipe, x, y = setup
    step, opt_state = train.make_train_step(pipe, optax.sgd(0.05), x)
    params = pipe.params
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses
    assert np.isfinite(losses).all()

    from jax.sharding import Mesh
    qmesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("stage",))
    qpipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, PARTITION,
                                     _stage_params(cfg, weights),
                                     qmesh, quant_bit=8)
    with pytest.raises(ValueError, match="not differentiable"):
        train.make_train_step(qpipe, optax.sgd(0.05), x)


def test_lm_training_gpt2_pipeline():
    """Causal-LM training through the pipeline: logits [M, B, S, V],
    shifted-id labels [M, B, S]; loss decreases under SGD."""
    import optax
    from jax.sharding import Mesh

    from pipeedge_tpu.models import gpt2 as gpt2_mod
    cfg = TransformerConfig(model_type="gpt2", hidden_size=32,
                            num_hidden_layers=2, num_attention_heads=4,
                            intermediate_size=64, layer_norm_eps=1e-5,
                            vocab_size=50, max_position_embeddings=32)
    partition = [(1, 4), (5, 8)]
    sp = [gpt2_mod.init_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == 8), seed=0)
        for l, r in partition]
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("stage",))
    pipe = spmd.build_spmd_pipeline(gpt2_mod.FAMILY, cfg, partition, sp,
                                    mesh)
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(0, 50, size=(3, 2, 9)), jnp.int32)
    inputs, labels = ids[..., :-1], ids[..., 1:]   # next-token targets
    step, opt_state = train.make_train_step(pipe, optax.sgd(0.1), inputs)
    params, losses = pipe.params, []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, inputs, labels)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.95, losses


def test_train_state_checkpoint_resume(setup, tmp_path):
    """save_train_state / restore_train_state round-trip the full
    training state (params + optimizer state + step) and training
    resumes identically: one more step from the restored state produces
    the same loss as continuing the original run."""
    import optax
    cfg, weights, pipe, x, y = setup
    opt = optax.adam(1e-3)   # stateful optimizer (momenta round-trip)
    step, opt_state = train.make_train_step(pipe, opt, x)
    params = pipe.params
    for i in range(2):
        params, opt_state, _ = step(params, opt_state, x, y)
    train.save_train_state(str(tmp_path / "ckpt"), params, opt_state, 2)
    params_cont, opt_cont, loss_cont = step(params, opt_state, x, y)

    # fresh structures (as a new process would build them)
    _, like_opt = train.make_train_step(pipe, opt, x)
    r_params, r_opt, r_step = train.restore_train_state(
        str(tmp_path / "ckpt"), pipe.params, like_opt)
    assert r_step == 2
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        r_params, params)
    # bit-continuous: the same compiled step on the same state on the
    # same backend — resumed training is exactly the uninterrupted run
    r_params2, _, r_loss = step(r_params, r_opt, x, y)
    assert float(r_loss) == float(loss_cont)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        r_params2, params_cont)


def test_dp_stage_training_grads_match(setup):
    """Data parallelism composes with pipeline training: a ('dp','stage')
    2x2 mesh produces the same gradients as the single-device oracle
    (the dp batch shard's gradient mean rides the program's transposes)."""
    from jax.sharding import Mesh
    cfg, weights, pipe, x, y = setup
    stage_params = _stage_params(cfg, weights)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("dp", "stage"))
    dpipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, PARTITION,
                                     stage_params, mesh)
    fwd = dpipe.compiled_for(x)
    n_blocks = dpipe.params["n_blocks"]

    def dloss(trainable):
        return train.softmax_xent(
            fwd({**trainable, "n_blocks": n_blocks}, x), y)

    trainable = {k: v for k, v in dpipe.params.items() if k != "n_blocks"}
    dval, dgrads = jax.value_and_grad(dloss)(trainable)

    ref_params, ref_loss = _single_device_loss(cfg, weights)
    rval, rgrads = jax.value_and_grad(ref_loss)(ref_params, x, y)
    np.testing.assert_allclose(float(dval), float(rval),
                               rtol=1e-5, atol=1e-6)
    got = np.asarray(dgrads["blocks"]["mlp_up"]["w"])
    want = np.asarray(rgrads["blocks"]["mlp_up"]["w"])
    for s in range(2):
        np.testing.assert_allclose(got[s], want[2 * s:2 * s + 2],
                                   rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(dgrads["final"]["head"]["w"]),
        np.asarray(rgrads["final"]["head"]["w"]), rtol=2e-4, atol=1e-5)


def test_lm_training_llama_pipeline():
    """LLaMA-family training through the pipeline (RoPE/RMSNorm/SwiGLU/
    GQA sublayers are differentiable as-is): loss decreases under SGD."""
    import optax
    from jax.sharding import Mesh

    from pipeedge_tpu.models import llama as llama_mod
    cfg = TransformerConfig(model_type="llama", hidden_size=32,
                            num_hidden_layers=2, num_attention_heads=4,
                            num_kv_heads=2, intermediate_size=64,
                            layer_norm_eps=1e-5, vocab_size=50,
                            max_position_embeddings=32)
    partition = [(1, 4), (5, 8)]
    sp = [llama_mod.init_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == 8), seed=0)
        for l, r in partition]
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("stage",))
    pipe = spmd.build_spmd_pipeline(llama_mod.FAMILY, cfg, partition, sp,
                                    mesh)
    rng = np.random.default_rng(4)
    ids = jnp.asarray(rng.integers(0, 50, size=(3, 2, 9)), jnp.int32)
    inputs, labels = ids[..., :-1], ids[..., 1:]
    step, opt_state = train.make_train_step(pipe, optax.sgd(0.1), inputs)
    params, losses = pipe.params, []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, inputs, labels)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.95, losses


@pytest.mark.fleet
def test_train_cli_multistage_dp_resume(tmp_path):
    """tools/train.py end-to-end in a subprocess: 2 stages x dp 2 mesh,
    adam, checkpoint at the end, then a second invocation resumes from
    the saved step and continues."""
    import os
    import re
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=repo)
    ck = str(tmp_path / "ck")
    cmd = [sys.executable, os.path.join(repo, "tools", "train.py"),
           "-m", "pipeedge/test-tiny-gpt2", "-pt", "1,4,5,8", "--dp", "2",
           "-b", "2", "-u", "2", "--seq-len", "8", "--optimizer", "adam",
           "--ckpt-dir", ck, "--log-every", "1"]
    first = subprocess.run(cmd + ["--steps", "3"], capture_output=True,
                           text=True, env=env, timeout=600)
    assert first.returncode == 0, first.stdout + first.stderr
    losses = [float(m) for m in
              re.findall(r"loss=([0-9.]+)", first.stdout)]
    assert len(losses) == 3 and losses[-1] < losses[0]

    second = subprocess.run(cmd + ["--steps", "5"], capture_output=True,
                            text=True, env=env, timeout=600)
    assert second.returncode == 0, second.stdout + second.stderr
    assert "resumed from" in second.stdout and "step 3" in second.stdout
    more = [float(m) for m in re.findall(r"loss=([0-9.]+)", second.stdout)]
    assert len(more) == 2 and more[-1] < losses[0]
    summary = json.loads(second.stdout.strip().splitlines()[-1])
    assert summary["steps"] == 2 and summary["mesh"] == {"dp": 2,
                                                         "stage": 2}


def test_bert_and_moe_training_learn():
    """The remaining families train through the pipeline too: BERT
    sequence classification (tanh pooler + head) and switch-MoE blocks
    (the top-1 gate probability scales the expert output, so routing
    passes gradients); loss decreases under SGD for both."""
    import optax
    from jax.sharding import Mesh

    from pipeedge_tpu.models import bert as bert_mod
    from pipeedge_tpu.models import gpt2 as gpt2_mod
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("stage",))
    rng = np.random.default_rng(6)

    bert_cfg = TransformerConfig(model_type="bert", hidden_size=32,
                                 num_hidden_layers=2, num_attention_heads=4,
                                 intermediate_size=64, layer_norm_eps=1e-12,
                                 vocab_size=60, max_position_embeddings=32,
                                 num_labels=2)
    moe_cfg = TransformerConfig(model_type="gpt2", hidden_size=32,
                                num_hidden_layers=2, num_attention_heads=4,
                                intermediate_size=64, layer_norm_eps=1e-5,
                                vocab_size=50, max_position_embeddings=32,
                                n_experts=4, capacity_factor=4.0)
    for name, (mod, cfg) in {"bert": (bert_mod, bert_cfg),
                             "moe": (gpt2_mod, moe_cfg)}.items():
        partition = [(1, 4), (5, 8)]
        sp = [mod.init_params(
            cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == 8), seed=0)
            for l, r in partition]
        pipe = spmd.build_spmd_pipeline(mod.FAMILY, cfg, partition, sp,
                                        mesh)
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(3, 2, 8)),
                          jnp.int32)
        if name == "bert":
            inputs = ids
            labels = jnp.asarray(rng.integers(0, 2, size=(3, 2)), jnp.int32)
        else:
            inputs, labels = ids[..., :-1], ids[..., 1:]
        step, opt_state = train.make_train_step(pipe, optax.sgd(0.1),
                                                inputs)
        params, losses = pipe.params, []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, inputs,
                                           labels)
            losses.append(float(loss))
        assert np.isfinite(losses).all(), (name, losses)
        # every step improves (bert's near-chance binary loss moves
        # slowly in absolute terms; monotonic descent is the real claim)
        assert all(b < a for a, b in zip(losses, losses[1:])), (name,
                                                                losses)


def test_sp_ring_attention_training_grads():
    """Long-context training: a ('stage','sp') pipeline with
    sequence-sharded activations and ring attention per block is
    differentiable — JAX transposes the ring ppermutes — and its
    gradients match the single-device oracle."""
    from jax.sharding import Mesh
    from transformers import BertConfig, BertForSequenceClassification

    from pipeedge_tpu.models import bert as bert_mod
    hf_cfg = BertConfig(hidden_size=32, num_hidden_layers=2,
                        num_attention_heads=4, intermediate_size=64,
                        vocab_size=60, max_position_embeddings=32,
                        num_labels=2)
    torch.manual_seed(1)
    model = BertForSequenceClassification(hf_cfg).eval()
    cfg = TransformerConfig(model_type="bert", hidden_size=32,
                            num_hidden_layers=2, num_attention_heads=4,
                            intermediate_size=64, layer_norm_eps=1e-12,
                            vocab_size=60, max_position_embeddings=32,
                            num_labels=2)
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    partition = [(1, 4), (5, 8)]
    sp_params = [bert_mod.load_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == 8), weights)
        for l, r in partition]
    mesh = spmd.make_pipeline_mesh(2, sp=2)
    pipe = spmd.build_spmd_pipeline(bert_mod.FAMILY, cfg, partition,
                                    sp_params, mesh)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.integers(0, 60, size=(3, 2, 8)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 2, size=(3, 2)), jnp.int32)
    fwd = pipe.compiled_for(x)
    n_blocks = pipe.params["n_blocks"]

    def sp_loss(trainable):
        return train.softmax_xent(
            fwd({**trainable, "n_blocks": n_blocks}, x), y)

    trainable = {k: v for k, v in pipe.params.items() if k != "n_blocks"}
    sp_val, sp_grads = jax.value_and_grad(sp_loss)(trainable)

    total = 8
    sc = ShardConfig(1, total, is_first=True, is_last=True)
    ref_params = bert_mod.load_params(cfg, sc, weights)
    fn = make_shard_fn(bert_mod.FAMILY, cfg, sc)

    def ref_loss(params):
        return train.softmax_xent(
            jnp.stack([fn(params, u) for u in x]), y)

    ref_val, ref_grads = jax.value_and_grad(ref_loss)(ref_params)
    np.testing.assert_allclose(float(sp_val), float(ref_val),
                               rtol=1e-5, atol=1e-6)
    got = np.asarray(sp_grads["blocks"]["q"]["w"])
    want = np.asarray(ref_grads["blocks"]["q"]["w"])
    for s in range(2):
        np.testing.assert_allclose(got[s], want[s:s + 1], rtol=2e-4,
                                   atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sp_grads["final"]["head"]["w"]),
        np.asarray(ref_grads["final"]["head"]["w"]), rtol=2e-4, atol=1e-5)


@pytest.mark.fleet
def test_train_cli_bert(tmp_path):
    """tools/train.py covers BERT sequence classification (round-4
    advice: the library always did; now the CLI agrees)."""
    import os
    import re
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=repo)
    cmd = [sys.executable, os.path.join(repo, "tools", "train.py"),
           "-m", "pipeedge/test-tiny-bert", "-pt", "1,4,5,8",
           "-b", "2", "-u", "2", "--seq-len", "8", "--steps", "3",
           "--log-every", "1"]
    run = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=600)
    assert run.returncode == 0, run.stdout + run.stderr
    losses = [float(m) for m in re.findall(r"loss=([0-9.]+)", run.stdout)]
    assert len(losses) == 3 and losses[-1] < losses[0]


def test_mixed_precision_training():
    """bf16-compute/f32-master mixed precision: params stay float32
    masters across updates, loss tracks the full-f32 run closely, and
    descends; bf16-param pipelines are refused (they have no masters)."""
    import optax
    from jax.sharding import Mesh

    from pipeedge_tpu.models import vit as vit_mod
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("stage",))
    cfg = TransformerConfig(model_type="vit", hidden_size=32,
                            num_hidden_layers=2, num_attention_heads=4,
                            intermediate_size=64, num_labels=5,
                            image_size=16, patch_size=4)
    partition = [(1, 4), (5, 8)]
    rng = np.random.default_rng(11)
    inputs = jnp.asarray(rng.normal(size=(3, 2, 3, 16, 16)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 5, size=(3, 2)), jnp.int32)

    def run(mixed):
        sp = [vit_mod.init_params(
            cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == 8),
            seed=0) for l, r in partition]
        pipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, partition,
                                        sp, mesh)
        step, opt_state = train.make_train_step(
            pipe, optax.sgd(0.1), inputs, mixed_precision=mixed)
        params, losses = pipe.params, []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, inputs,
                                           labels)
            losses.append(float(loss))
        return params, losses

    params_mp, losses_mp = run(True)
    _, losses_fp = run(False)
    assert all(np.isfinite(losses_mp)), losses_mp
    assert losses_mp[-1] < losses_mp[0], losses_mp
    # master weights never degrade to bf16 across updates
    for leaf in jax.tree_util.tree_leaves(
            {k: v for k, v in params_mp.items() if k != "n_blocks"}):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32, leaf.dtype
    # the bf16 compute path tracks full precision closely on this scale
    np.testing.assert_allclose(losses_mp, losses_fp, rtol=0.05)

    # a bf16-param pipeline has no f32 masters: refused with guidance
    sp16 = [vit_mod.init_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == 8),
        seed=0, dtype=jnp.bfloat16) for l, r in partition]
    pipe16 = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, partition,
                                      sp16, mesh)
    with pytest.raises(ValueError, match="float32"):
        train.make_train_step(pipe16, optax.sgd(0.1), inputs,
                              mixed_precision=True)
