"""Int8 compute path (ops/int8_matmul.py + the QuantizeCompute routing in
models/layers.py): kernel-vs-XLA parity in interpret mode (incl. all-zero
blocks and saturating outliers), the wire-tunnel activation-exactness
contract, config/env/setter semantics, and the end-to-end tunnel through
build_pipeline on the tiny ViT fixture."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pipeedge_tpu.models import layers  # noqa: E402
from pipeedge_tpu.ops import int8_matmul, quant as quant_ops  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_quantize_state(monkeypatch):
    """Tests toggle trace-time process globals; leave no residue."""
    monkeypatch.delenv("PIPEEDGE_QUANTIZE_COMPUTE", raising=False)
    monkeypatch.delenv("PIPEEDGE_QUANTIZE_SKIP", raising=False)
    monkeypatch.delenv(int8_matmul.ENV_INT8_MATMUL, raising=False)
    prev = layers._QUANTIZE_COMPUTE
    yield
    layers.set_quantize_compute(prev)


def _quantized(m=16, k=256, n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    x_q, x_s = int8_matmul.quantize_act_blocks(x, 128)
    w_q, w_s = int8_matmul.quantize_weight(w)
    return x, w, x_q, x_s, w_q, w_s


# -- kernel parity -------------------------------------------------------

def test_interpret_kernel_matches_xla_reference():
    _, _, x_q, x_s, w_q, w_s = _quantized()
    got = int8_matmul.matmul_pallas(x_q, x_s, w_q, w_s, 128,
                                    interpret=True)
    ref = int8_matmul.matmul_xla(x_q, x_s, w_q, w_s, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_block_scaled_math_tracks_exact_matmul():
    x, w, x_q, x_s, w_q, w_s = _quantized()
    got = np.asarray(int8_matmul.matmul_xla(x_q, x_s, w_q, w_s, 128))
    exact = np.asarray(x @ w)
    rel = np.abs(got - exact).max() / np.abs(exact).max()
    assert rel < 0.05, rel


def test_all_zero_blocks_decode_exactly():
    """Scale-1 guard: zero activations/channels must stay exactly zero
    (a 0/0 scale would NaN the whole tile)."""
    x = jnp.zeros((8, 256), jnp.float32)
    w = jnp.zeros((256, 32), jnp.float32)
    x_q, x_s = int8_matmul.quantize_act_blocks(x, 128)
    w_q, w_s = int8_matmul.quantize_weight(w)
    assert np.all(np.asarray(x_s) == 1.0)
    assert np.all(np.asarray(w_s) == 1.0)
    y = int8_matmul.matmul_pallas(x_q, x_s, w_q, w_s, 128, interpret=True)
    assert np.all(np.asarray(y) == 0.0)


def test_saturating_outlier_clips_and_stays_blockwise():
    """A huge outlier saturates its own k-block's scale; other blocks'
    quantization is untouched (the point of block scaling)."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(4, 256)).astype(np.float32)
    x[0, 3] = 1e4                                   # outlier in block 0
    x_q, x_s = int8_matmul.quantize_act_blocks(jnp.asarray(x), 128)
    x_q, x_s = np.asarray(x_q), np.asarray(x_s)
    assert x_q.min() >= -127 and x_q.max() <= 127
    assert x_s[0, 0] == pytest.approx(1e4 / 127.0)
    # row 0 block 1 scale is outlier-free (pure ~N(0,1) amax)
    assert x_s[0, 1] < 0.1
    # other rows completely unaffected
    ref_q, ref_s = int8_matmul.quantize_act_blocks(jnp.asarray(x[1:]), 128)
    np.testing.assert_array_equal(x_q[1:], np.asarray(ref_q))
    np.testing.assert_array_equal(x_s[1:], np.asarray(ref_s))


def test_int8_dense_shapes_bias_and_clamp():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 5, 96)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(96, 32)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    y = int8_matmul.int8_dense(x, w, b)
    assert y.shape == (2, 5, 32) and y.dtype == jnp.bfloat16
    exact = np.asarray(x.astype(jnp.float32) @ w + b)
    got = np.asarray(y, np.float32)
    assert np.abs(got - exact).max() / np.abs(exact).max() < 0.1
    # a clamp alpha below the data range changes the result (it's applied)
    y_cl = int8_matmul.int8_dense(x, w, b, clamp_alpha=0.1)
    assert not np.array_equal(np.asarray(y_cl, np.float32), got)


def test_mode_env_dispatch(monkeypatch):
    monkeypatch.setenv(int8_matmul.ENV_INT8_MATMUL, "off")
    assert not int8_matmul.kernel_available()
    monkeypatch.setenv(int8_matmul.ENV_INT8_MATMUL, "interpret")
    assert int8_matmul.kernel_available()
    monkeypatch.setenv(int8_matmul.ENV_INT8_MATMUL, "auto")
    if jax.default_backend() != "tpu":
        assert not int8_matmul.kernel_available()   # XLA reference path


# -- wire tunnel (consumer side) ----------------------------------------

def test_wire_dense_activation_side_is_exact():
    """The affine identity: wire_dense == decode-then-matmul against the
    DEQUANTIZED weight — the activation side loses nothing; only the
    weight quantization deviates from the f32 dense."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(6, 4, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128, 48)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(48,)), jnp.float32)
    enc = quant_ops.tensor_encode_outerdim(x, 8)
    got = np.asarray(int8_matmul.wire_dense({"w": w, "b": b}, enc))
    w_q, w_s = int8_matmul.quantize_weight(w)
    w_deq = np.asarray(w_q, np.float32) * np.asarray(w_s)[None, :]
    x_deq = np.asarray(quant_ops.tensor_decode_outerdim(enc))
    ref = x_deq.reshape(-1, 128) @ w_deq + np.asarray(b)
    np.testing.assert_allclose(got.reshape(-1, 48), ref,
                               rtol=1e-4, atol=1e-4)
    assert got.shape == (6, 4, 48)


def test_wire_dense_rejects_non_8bit():
    x = jnp.ones((2, 2, 128), jnp.float32)
    enc = quant_ops.tensor_encode_outerdim(x, 4)
    with pytest.raises(ValueError, match="8-bit"):
        int8_matmul.wire_dense({"w": jnp.ones((128, 8)),
                                "b": jnp.zeros((8,))}, enc)


# -- QuantizeCompute config semantics -----------------------------------

def test_quantize_compute_setter_env_and_skip(monkeypatch):
    assert not layers.quantize_compute().enabled       # default off
    monkeypatch.setenv("PIPEEDGE_QUANTIZE_COMPUTE", "1")
    monkeypatch.setenv("PIPEEDGE_QUANTIZE_SKIP", "attn.out,mlp.down")
    layers.set_quantize_compute(None)                  # defer to env
    qc = layers.quantize_compute()
    assert qc.enabled and qc.skip_tags == {"attn.out", "mlp.down"}
    # the programmatic setter beats the env (the pin an A/B needs)
    layers.set_quantize_compute(False)
    assert not layers.quantize_compute().enabled
    cfg = layers.QuantizeCompute(enabled=True, block_k=64,
                                 clamp_alphas={"mlp.up": 2.5})
    layers.set_quantize_compute(cfg)
    assert layers.quantize_compute() is cfg


def test_tagged_dense_routes_and_untagged_stays_exact():
    rng = np.random.default_rng(4)
    p = {"w": jnp.asarray(rng.normal(size=(128, 32)), jnp.float32),
         "b": jnp.zeros((32,), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(3, 128)), jnp.float32)
    exact = np.asarray(layers.dense(p, x))
    layers.set_quantize_compute(layers.QuantizeCompute(enabled=True))
    tagged = np.asarray(layers.dense(p, x, tag="mlp.up"))
    untagged = np.asarray(layers.dense(p, x))
    skipped = None
    layers.set_quantize_compute(layers.QuantizeCompute(
        enabled=True, skip_tags=frozenset({"mlp.up"})))
    skipped = np.asarray(layers.dense(p, x, tag="mlp.up"))
    np.testing.assert_array_equal(untagged, exact)     # untagged: exact
    np.testing.assert_array_equal(skipped, exact)      # opt-out: exact
    assert not np.array_equal(tagged, exact)           # routed: quantized
    assert np.abs(tagged - exact).max() / np.abs(exact).max() < 0.05


def test_observer_sees_tagged_activations():
    seen = []
    rng = np.random.default_rng(5)
    p = {"w": jnp.asarray(rng.normal(size=(64, 16)), jnp.float32),
         "b": jnp.zeros((16,), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)
    prev = layers._QC_OBSERVER
    layers._QC_OBSERVER = lambda tag, a: seen.append(tag)
    try:
        layers.dense(p, x, tag="attn.q")
        layers.dense(p, x)                             # untagged: silent
    finally:
        layers._QC_OBSERVER = prev
    assert seen == ["attn.q"]


# -- end-to-end tunnel through build_pipeline ---------------------------

MODEL = "pipeedge/test-tiny-vit"


def _tiny_images(batch=8, seed=0):
    from pipeedge_tpu.models import registry
    cfg = registry.get_model_config(MODEL)
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(
        batch, cfg.num_channels, cfg.image_size, cfg.image_size)),
        jnp.float32)


def test_tunnel_stage_consumes_wire_payload_without_dequant():
    """build_pipeline with tunnel=True + an 8-bit edge: stage 1 is marked
    tunnel, runs, and its logits track the non-tunnel quantized pipeline
    (same wire bytes in, int8-weight deviation only)."""
    from pipeedge_tpu.parallel import pipeline as pl_mod

    x = _tiny_images()
    layers.set_quantize_compute(layers.QuantizeCompute(
        enabled=True, tunnel=True))
    try:
        stages = pl_mod.build_pipeline(MODEL, [(1, 1), (2, 8)],
                                       quant_bits=[8]).stages
        assert [s.tunnel for s in stages] == [False, True]
        payload = stages[0](x)
        logits_tunnel = np.asarray(stages[1](payload))
    finally:
        layers.set_quantize_compute(None)

    # reference: same int8 compute + 8-bit edge, but decode-then-matmul
    layers.set_quantize_compute(layers.QuantizeCompute(enabled=True))
    try:
        ref_stages = pl_mod.build_pipeline(MODEL, [(1, 1), (2, 8)],
                                           quant_bits=[8]).stages
        assert [s.tunnel for s in ref_stages] == [False, False]
        logits_ref = np.asarray(ref_stages[1](ref_stages[0](x)))
    finally:
        layers.set_quantize_compute(None)
    assert logits_tunnel.shape == logits_ref.shape
    # the tunnel's only deviation is consuming the identical wire bytes
    # on the MXU directly; agreement with the decode-first route is tight
    assert np.abs(logits_tunnel - logits_ref).max() < 0.05
    assert np.mean(np.argmax(logits_tunnel, -1)
                   == np.argmax(logits_ref, -1)) >= 0.99


def test_tunnel_gating_requires_wire_sub_boundary():
    """A partition split at a non-wire sublayer (layer_start % 4 not in
    wire_subs) must NOT tunnel even when asked to."""
    from pipeedge_tpu.parallel import pipeline as pl_mod

    layers.set_quantize_compute(layers.QuantizeCompute(
        enabled=True, tunnel=True))
    try:
        # layer_start=4 -> (4-1)%4 == 3 in wire_subs -> tunnel
        stages = pl_mod.build_pipeline(MODEL, [(1, 3), (4, 8)],
                                       quant_bits=[8]).stages
        assert stages[1].tunnel
        # layer_start=3 -> (3-1)%4 == 2 not in wire_subs -> no tunnel
        stages = pl_mod.build_pipeline(MODEL, [(1, 2), (3, 8)],
                                       quant_bits=[8]).stages
        assert not stages[1].tunnel
        # 4-bit edge: wire_dense can't consume it -> no tunnel
        stages = pl_mod.build_pipeline(MODEL, [(1, 3), (4, 8)],
                                       quant_bits=[4]).stages
        assert not stages[1].tunnel
    finally:
        layers.set_quantize_compute(None)


@pytest.mark.parametrize("clamp", ["off", "inline", "sidecar"])
def test_int8_compute_top1_agreement_on_fixture(clamp, tmp_path):
    """Pure int8 compute (no wire edge) agrees >= 0.99 top-1 with exact
    on the tiny fixture, in process: with dynamic block scales alone,
    with Banner clamp alphas calibrated from the first batch, and with
    the same alphas read back from a sidecar file (what
    `tools/calibrate.py` writes and a server loads)."""
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.utils import calibrate

    x = _tiny_images(batch=16)
    fn, params, _ = registry.module_shard_factory(
        MODEL, None, 1, registry.get_model_layers(MODEL))
    raw = fn.__wrapped__
    exact = np.asarray(jax.jit(raw)(params, x))
    qc = layers.QuantizeCompute(enabled=True)
    if clamp != "off":
        alphas, _, _ = calibrate.calibrate_shard(
            MODEL, None, 1, registry.get_model_layers(MODEL),
            [np.asarray(x[:8])])
        assert alphas and all(a > 0 for a in alphas.values())
        if clamp == "sidecar":
            path = str(tmp_path / "tiny.int8scales.npz")
            calibrate.write_sidecar(path, alphas, {}, meta={"bit": 8})
            qc = calibrate.quantize_compute_from_sidecar(path)
            assert qc.clamp_alphas == pytest.approx(alphas)
        else:
            qc = layers.QuantizeCompute(enabled=True, clamp_alphas=alphas)
    layers.set_quantize_compute(qc)
    try:
        q = np.asarray(jax.jit(raw)(params, x))
    finally:
        layers.set_quantize_compute(None)
    assert np.mean(np.argmax(exact, -1) == np.argmax(q, -1)) >= 0.99
