"""Quantized ICI collectives (ops/qcollectives.py): error bounds,
determinism, the tensor.py psum gate, and the wire-footprint tally.

Runs on the conftest's 8-device virtual CPU mesh — the ring ppermute
implementation is the portable path (utils/jax_compat.py), so the CPU
mesh exercises exactly the collective the TPU runs.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from pipeedge_tpu.ops import qcollectives
from pipeedge_tpu.utils import jax_compat


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("tp",))


def _shards(n, m, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.normal(size=(n, m)) * scale)
                       .astype(np.float32))


def _qpsum_fn(mesh, bit, **kw):
    return jax.jit(jax_compat.shard_map(
        partial(qcollectives.qpsum, axis_name="tp", bit=bit, **kw),
        mesh=mesh, in_specs=P("tp"), out_specs=P("tp")))


def _qag_fn(mesh, bit, **kw):
    return jax.jit(jax_compat.shard_map(
        partial(qcollectives.qall_gather, axis_name="tp", bit=bit, **kw),
        mesh=mesh, in_specs=P("tp"), out_specs=P(None)))


def test_qpsum_bit0_is_exact_psum():
    mesh = _mesh(2)
    x = _shards(2, 512)
    got = np.asarray(_qpsum_fn(mesh, 0)(x))
    exact = np.asarray(x).sum(axis=0)
    assert np.array_equal(got, np.stack([exact, exact]))


@pytest.mark.parametrize("bit", [8, 4])
@pytest.mark.parametrize("n", [2, 4])
def test_qpsum_within_error_bound(bit, n):
    mesh = _mesh(n)
    x = _shards(n, 1024, seed=bit * 10 + n)
    got = np.asarray(_qpsum_fn(mesh, bit)(x))
    exact = np.asarray(x).sum(axis=0)
    absrange = float(max(np.asarray(x)[i].max() - np.asarray(x)[i].min()
                         for i in range(n)))
    bound = qcollectives.qpsum_error_bound(absrange, bit, n)
    err = np.abs(got - exact[None]).max()
    assert err <= bound, (err, bound)
    # and the quantization is actually doing something at int4 (not a
    # silently exact path pretending to compress)
    if bit == 4:
        assert err > 0


@pytest.mark.parametrize("bit", [8, 4])
def test_qpsum_deterministic(bit):
    mesh = _mesh(4)
    x = _shards(4, 768, seed=7)
    fn = _qpsum_fn(mesh, bit)
    a = np.asarray(fn(x))
    b = np.asarray(fn(x))
    assert np.array_equal(a, b)


def test_qpsum_odd_length_and_dtype():
    """Non-block-aligned flat sizes zero-pad internally; bf16 inputs come
    back bf16 with f32 internal accumulation."""
    mesh = _mesh(2)
    x = _shards(2, 333).astype(jnp.bfloat16)
    got = _qpsum_fn(mesh, 8)(x)
    assert got.dtype == jnp.bfloat16
    assert got.shape == (2, 333)
    exact = np.asarray(x.astype(jnp.float32)).sum(axis=0)
    absrange = float(np.abs(np.asarray(x.astype(jnp.float32))).max()) * 2
    bound = qcollectives.qpsum_error_bound(absrange, 8, 2) \
        + np.abs(exact).max() * 2 ** -7  # bf16 output round-off
    assert np.abs(np.asarray(got, np.float32) - exact[None]).max() <= bound


def test_qpsum_multidim_shape_preserved():
    mesh = _mesh(2)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 4, 19, 32)).astype(np.float32))
    got = np.asarray(jax.jit(jax_compat.shard_map(
        partial(qcollectives.qpsum, axis_name="tp", bit=8),
        mesh=mesh, in_specs=P("tp"), out_specs=P("tp")))(x))
    assert got.shape == x.shape
    exact = np.asarray(x).sum(axis=0)
    assert np.abs(got - exact[None]).max() < 0.1


def test_qpsum_clamped_path_runs():
    mesh = _mesh(2)
    x = _shards(2, 512, seed=11)
    got = np.asarray(_qpsum_fn(mesh, 8, clamp=True)(x))
    exact = np.asarray(x).sum(axis=0)
    # Banner clamp trades bounded bias for a smaller step: still close
    assert np.abs(got - exact[None]).max() < 0.5


def test_qpsum_invalid_bit():
    with pytest.raises(ValueError):
        qcollectives.qpsum(jnp.zeros((4,)), "tp", 6)


@pytest.mark.parametrize("bit", [8, 4])
def test_qall_gather_tiled(bit):
    mesh = _mesh(4)
    x = _shards(4, 256, seed=5).reshape(4, 1, 256)
    got = np.asarray(_qag_fn(mesh, bit, axis=1, tiled=True)(x))
    # per-device shard [1, 1, 256]; tiled gather along axis 1 -> [1, 4, 256]
    assert got.shape == (1, 4, 256)
    full = np.concatenate([np.asarray(x)[i] for i in range(4)], axis=0)
    levels = (1 << bit) - 1
    per_shard_range = max(float(np.ptp(np.asarray(x)[i]))
                          for i in range(4))
    tol = per_shard_range / levels / 2 + 1e-5
    assert np.abs(got.reshape(full.shape) - full).max() <= tol


def test_qall_gather_stacked():
    mesh = _mesh(2)
    x = _shards(2, 64, seed=6)
    got = np.asarray(jax.jit(jax_compat.shard_map(
        partial(qcollectives.qall_gather, axis_name="tp", bit=8,
                axis=0, tiled=False),
        mesh=mesh, in_specs=P("tp"), out_specs=P(None)))(x))
    # per-device shard is [1, 64]; tiled=False stacks a new leading axis
    # (the jax.lax.all_gather contract)
    assert got.shape == (2, 1, 64)
    tol = max(float(np.ptp(np.asarray(x)[i])) for i in range(2)) / 255 / 2 \
        + 1e-5
    assert np.abs(got.reshape(2, 64) - np.asarray(x)).max() <= tol


def test_qall_gather_bit0_exact():
    mesh = _mesh(2)
    x = _shards(2, 64).reshape(2, 1, 64)
    got = np.asarray(_qag_fn(mesh, 0, axis=1, tiled=True)(x))
    full = np.concatenate([np.asarray(x)[i] for i in range(2)], axis=0)
    assert np.array_equal(got.reshape(full.shape), full)


# -- tensor.py psum gate --------------------------------------------------

def test_tp_quant_bits_flag_roundtrip():
    from pipeedge_tpu.parallel import tensor
    assert tensor.get_tp_quant_bits() == 0
    tensor.set_tp_quant_bits(8)
    try:
        assert tensor.get_tp_quant_bits() == 8
    finally:
        tensor.set_tp_quant_bits(0)
    with pytest.raises(ValueError):
        tensor.set_tp_quant_bits(3)


def test_tp_block_quantized_close_to_exact():
    """The Megatron block body with quantized psums stays within a tight
    activation tolerance of the exact body — the numerics claim behind
    the near-1.0 top-1 agreement target (ROADMAP item 2)."""
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import tensor

    cfg = registry.get_model_entry("pipeedge/test-tiny-vit").config
    rng = np.random.default_rng(0)
    bp = registry.module_shard_factory(
        "pipeedge/test-tiny-vit", None, 1, 4, dtype=jnp.float32,
        unroll=True)[1]["blocks"][0]
    mesh = _mesh(2)
    sharded = tensor.shard_block_params(cfg, bp, mesh)
    x = jnp.asarray(rng.normal(size=(2, 17, cfg.hidden_size))
                    .astype(np.float32))
    exact = np.asarray(tensor.make_tp_block_fn(cfg, mesh)(sharded, x))
    tensor.set_tp_quant_bits(8)
    try:
        quant = np.asarray(tensor.make_tp_block_fn(cfg, mesh)(sharded, x))
    finally:
        tensor.set_tp_quant_bits(0)
    assert not np.array_equal(exact, quant)      # the gate actually flips
    scale = max(1.0, float(np.abs(exact).max()))
    assert np.abs(exact - quant).max() / scale < 0.05


# -- wire-footprint tally + telemetry ------------------------------------

def test_tally_records_sites_and_reduction():
    qcollectives.reset_trace_tally()
    mesh = _mesh(2)
    x = _shards(2, 1024, seed=9)
    np.asarray(_qpsum_fn(mesh, 8)(x))
    np.asarray(_qag_fn(mesh, 4, axis=0, tiled=False)(
        x.reshape(2, 1, 1024)))
    tally = qcollectives.trace_tally()
    kinds = {t["kind"] for t in tally}
    assert kinds == {"psum", "all_gather"}
    for t in tally:
        assert 0 < t["wire_bytes"] < t["raw_bytes"]
    ps = next(t for t in tally if t["kind"] == "psum")
    # int8 block-scaled payload: ~4x smaller minus scale/shift metadata
    assert 3.5 < ps["raw_bytes"] / ps["wire_bytes"] < 4.0
    qcollectives.reset_trace_tally()


def test_record_collectives_spans_and_metrics():
    from pipeedge_tpu import telemetry
    qcollectives.reset_trace_tally()
    mesh = _mesh(2)
    np.asarray(_qpsum_fn(mesh, 4)(_shards(2, 512, seed=13)))
    before = qcollectives.COLLECTIVE_BITS_TOTAL.total()
    rec = telemetry.configure(rank=0)
    try:
        summary = qcollectives.record_collectives(executions=3)
    finally:
        spans = rec.snapshot()
        telemetry.disable()
    assert summary["sites"] == 1
    assert summary["wire_bits_total"] > 0
    assert summary["wire_reduction"] > 7      # int4: ~8x minus metadata
    col = [s for s in spans if s["cat"] == "collective"]
    assert len(col) == 1
    name = col[0]["name"]
    assert name.startswith("psum4:")
    # the span name carries the run-total wire bytes (report.py parses it)
    assert int(name.split(":")[1]) * 8 == summary["wire_bits_total"]
    assert qcollectives.COLLECTIVE_BITS_TOTAL.total() - before \
        == summary["wire_bits_total"]
    qcollectives.reset_trace_tally()


def test_report_collectives_section():
    """analyze_spans folds collective spans into the per-stage bits-moved
    section (tools/trace_report.py consumes it)."""
    from pipeedge_tpu.telemetry import report

    t = 1_000_000
    spans = [
        {"cat": "collective", "name": "psum8:1024", "t0": t, "t1": t,
         "rank": 0, "stage": 0},
        {"cat": "collective", "name": "all_gather8:512", "t0": t, "t1": t,
         "rank": 0, "stage": 1},
        {"cat": "stage", "name": "dispatch", "t0": t, "t1": t + 10_000,
         "rank": 0, "stage": 0, "mb": 0},
        {"cat": "wire", "name": "send->r1", "t0": t, "t1": t + 5_000,
         "rank": 0},
    ]
    rec = report.analyze_spans(spans, span_cost_ns=100.0)
    col = rec["collectives"]
    assert col["sites"] == 2
    assert col["wire_bytes"] == 1536
    assert col["by_kind"] == {"all_gather8": 512, "psum8": 1024}
    assert col["per_stage"]["stage0"]["wire_bytes"] == 1024
    assert col["per_stage"]["stage1"]["wire_bytes"] == 512
    assert col["dcn_edge_busy_s"] > 0


def test_error_bound_monotonic():
    """More shards and fewer bits both widen the bound."""
    b84 = qcollectives.qpsum_error_bound(1.0, 8, 4)
    b88 = qcollectives.qpsum_error_bound(1.0, 8, 8)
    b44 = qcollectives.qpsum_error_bound(1.0, 4, 4)
    assert b84 < b88
    assert b84 < b44


@pytest.mark.parametrize("n_tp,bit", [(2, 8), (4, 8), (2, 4)])
def test_tp_model_quantized_top1_agreement(n_tp, bit):
    """The whole tiny ViT with every block's Megatron psums quantized
    (exact math, quantized comms): its logits pick the exact model's
    top-1 on >= 0.99 of a seeded batch, and the trace tally counts two
    psum sites a block, each moving fewer bytes than the raw psum."""
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import tensor

    name = "pipeedge/test-tiny-vit"
    entry = registry.get_model_entry(name)
    cfg, family = entry.config, entry.family
    assert cfg.num_attention_heads % n_tp == 0
    params = registry.module_shard_factory(
        name, None, 1, registry.get_model_layers(name),
        dtype=jnp.float32, unroll=True)[1]
    mesh = _mesh(n_tp)
    blocks = tuple(tensor.shard_block_params(cfg, bp, mesh)
                   for bp in params["blocks"])
    specs, local = tensor.family_tp_plan(cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(
        32, cfg.num_channels, cfg.image_size, cfg.image_size)), jnp.float32)

    def logits(mode_bits):
        # the bitwidth binds at trace time: a fresh body and wrapper
        tensor.set_tp_quant_bits(mode_bits)
        try:
            body = jax_compat.shard_map(
                partial(local, cfg=cfg, axis="tp"), mesh=mesh,
                in_specs=(specs, P()), out_specs=P())

            @jax.jit
            def run(ep, fp, bps, x):
                h = family.embed(ep, x, cfg)
                for bp in bps:
                    h = body(bp, h)
                return family.finalize(fp, h, cfg)

            return np.asarray(run(params["embeddings"], params["final"],
                                  blocks, x))
        finally:
            tensor.set_tp_quant_bits(0)

    exact = logits(0)
    qcollectives.reset_trace_tally()
    quant = logits(bit)
    tally = qcollectives.trace_tally()
    qcollectives.reset_trace_tally()
    assert not np.array_equal(exact, quant)
    assert np.mean(exact.argmax(-1) == quant.argmax(-1)) >= 0.99
    assert len(tally) == 2 * len(blocks)
    assert all(t["kind"] == "psum" and 0 < t["wire_bytes"] < t["raw_bytes"]
               for t in tally)
