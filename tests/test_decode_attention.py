"""Fused int8 decode-attention Pallas kernel vs the XLA dequantize path.

The kernel (ops/decode_attention.py) must reproduce the XLA int8 decode
step's semantics: dequantized cache reads, EXACT fresh-row substitution,
[0, pos] masking. Interpret mode on CPU; the same code lowers natively
on TPU (tests/test_chip_compile.py compiles it for the described
chip)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pipeedge_tpu.models import registry, stage_cache
from pipeedge_tpu.ops import decode_attention
from pipeedge_tpu.parallel import decode


pytestmark = pytest.mark.slow   # compile-heavy decode programs


@pytest.mark.parametrize("variant", [1, 2])
def test_kernel_matches_xla_dequant_attend(variant):
    """Direct kernel check against the reference computation — both the
    per-cell grid (v1) and the batch-as-sublane grid (v2)."""
    rng = np.random.default_rng(0)
    b, t, h, d = 2, 24, 4, 16
    pos = 13
    k_rows = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    v_rows = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)

    kq, ks, kz = stage_cache.quantize_rows(k_rows)
    vq, vs, vz = stage_cache.quantize_rows(v_rows)

    got = decode_attention.int8_decode_attention(
        q, kq, ks, kz, vq, vs, vz, k_new, v_new, pos, interpret=True,
        variant=variant)

    # reference: the XLA path's math
    k = stage_cache.dequantize_rows(kq, ks, kz, jnp.float32)
    v = stage_cache.dequantize_rows(vq, vs, vz, jnp.float32)
    k = k.at[:, pos:pos + 1].set(k_new)
    v = v.at[:, pos:pos + 1].set(v_new)
    keep = (jnp.arange(t) <= pos)[None, :]
    cfg = registry.get_model_config("pipeedge/test-tiny-gpt2")
    want = stage_cache.attend(q, k, v, keep, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_int8_pipeline_tokens_match_with_kernel(monkeypatch):
    """End-to-end: the int8 pipeline generates the same tokens with the
    fused kernel (interpret mode) as with the XLA dequantize path."""
    name = "pipeedge/test-tiny-gpt2"
    cfg = registry.get_model_config(name)
    total = registry.get_model_layers(name)
    _, params, _ = registry.module_shard_factory(name, None, 1, total,
                                                 unroll=False)
    fam = registry.get_model_entry(name).family.FAMILY
    rng = np.random.default_rng(5)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 8))

    def generate():
        pipe = decode.DecodePipeline(fam, cfg, [(1, total)], [params],
                                     max_len=32, cache_bits=8)
        return np.asarray(pipe.generate(ids, 10))

    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "0")
    want = generate()
    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "1")
    got = generate()
    np.testing.assert_array_equal(got, want)
    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "2")
    got_v2 = generate()                  # batch-as-sublane variant
    np.testing.assert_array_equal(got_v2, want)


def test_kernel_gate_scope(monkeypatch):
    """The kernel only takes the MHA single-token path: spans, GQA,
    sliding-window, and VMEM-overflowing windows stay on the XLA path
    (gate returns None). The opt-in is resolved ONCE at pipeline
    construction (`_int8_kernel_env`) and passed in, so env toggles after
    stage programs compile cannot desynchronize cached shapes."""
    import dataclasses
    cfg = registry.get_model_config("pipeedge/test-tiny-gpt2")
    cache8 = {"k_scale": None}
    # span / fp cache / GQA / window / huge window never route, even
    # when opted in
    assert decode._use_int8_decode_kernel(cache8, 2, cfg, 64, 1) is None
    assert decode._use_int8_decode_kernel({}, 1, cfg, 64, 1) is None
    gqa = dataclasses.replace(cfg, num_kv_heads=2, num_attention_heads=4)
    assert decode._use_int8_decode_kernel(cache8, 1, gqa, 64, 1) is None
    windowed = dataclasses.replace(cfg, sliding_window=4)
    assert decode._use_int8_decode_kernel(cache8, 1, windowed, 64,
                                          1) is None
    huge = decode._INT8_KERNEL_VMEM_CAP // (cfg.kv_heads * cfg.head_dim) + 8
    assert decode._use_int8_decode_kernel(cache8, 1, cfg, huge, 1) is None
    # opt-in off: the eligible shape stays on the XLA path; on: interpret
    # mode on this TPU-less host, kernel variant passed through
    assert decode._use_int8_decode_kernel(cache8, 1, cfg, 64, 0) is None
    assert decode._use_int8_decode_kernel(cache8, 1, cfg, 64, 1) == (True, 1)
    assert decode._use_int8_decode_kernel(cache8, 1, cfg, 64, 2) == (True, 2)
    # env resolution: unset/empty/0/off mean off; '2' selects variant 2;
    # anything else truthy means variant 1
    monkeypatch.delenv("PIPEEDGE_INT8_DECODE_ATTEND", raising=False)
    assert decode._int8_kernel_env() == 0
    for off in ("", "0", "false", "no", "off"):
        monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", off)
        assert decode._int8_kernel_env() == 0
    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "1")
    assert decode._int8_kernel_env() == 1
    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "2")
    assert decode._int8_kernel_env() == 2


def test_kernel_optin_bound_at_construction(monkeypatch):
    """Toggling the env var AFTER a pipeline is built must not change its
    routing: the flag is captured at construction (round-4 advice)."""
    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "1")
    name = "pipeedge/test-tiny-gpt2"
    cfg = registry.get_model_config(name)
    total = registry.get_model_layers(name)
    _, params, _ = registry.module_shard_factory(name, None, 1, total,
                                                 unroll=False)
    fam = registry.get_model_entry(name).family.FAMILY
    pipe = decode.DecodePipeline(fam, cfg, [(1, total)], [params],
                                 max_len=32, cache_bits=8)
    assert pipe.int8_decode_optin == 1
    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "0")
    assert pipe.int8_decode_optin == 1   # captured, not re-read
    monkeypatch.delenv("PIPEEDGE_INT8_DECODE_ATTEND", raising=False)
    pipe2 = decode.DecodePipeline(fam, cfg, [(1, total)], [params],
                                  max_len=32, cache_bits=8)
    assert pipe2.int8_decode_optin == 0


@pytest.mark.slow
def test_int8_pipeline_bf16_tokens_match_with_kernel(monkeypatch):
    """bf16 pipeline (the realistic serving dtype): kernel and XLA paths
    share cast points (dequant -> dtype, probs -> dtype), so tokens
    match on the tiny model; the flash-style softmax ordering is the
    only remaining numeric difference."""
    name = "pipeedge/test-tiny-gpt2"
    cfg = registry.get_model_config(name)
    total = registry.get_model_layers(name)
    _, params, _ = registry.module_shard_factory(name, None, 1, total,
                                                 dtype=jnp.bfloat16,
                                                 unroll=False)
    fam = registry.get_model_entry(name).family.FAMILY
    rng = np.random.default_rng(9)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 8))

    def generate():
        pipe = decode.DecodePipeline(fam, cfg, [(1, total)], [params],
                                     max_len=32, cache_bits=8,
                                     dtype=jnp.bfloat16)
        return np.asarray(pipe.generate(ids, 10))

    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "0")
    want = generate()
    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "1")
    got = generate()
    np.testing.assert_array_equal(got, want)


def test_kernel_auto_policy(monkeypatch):
    """PIPEEDGE_INT8_DECODE_ATTEND=auto routes kernel v2 ONLY at attend
    widths <= 256 (the 3/3-session measured crossover); wider windows
    stay on the XLA path, and shapes whose whole-batch block can't fit
    VMEM fall back to XLA too."""
    cfg = registry.get_model_config("pipeedge/test-tiny-gpt2")
    cache8 = {"k_scale": None}
    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "auto")
    assert decode._int8_kernel_env() == 3
    # small window -> v2; wide window -> XLA (None)
    assert decode._use_int8_decode_kernel(cache8, 1, cfg, 256, 3,
                                          batch=2) == (True, 2)
    assert decode._use_int8_decode_kernel(cache8, 1, cfg, 512, 3,
                                          batch=2) is None
    # whole-batch block can't fit -> XLA rather than dying in Mosaic
    assert decode._use_int8_decode_kernel(cache8, 1, cfg, 256, 3,
                                          batch=100000) is None

    # end-to-end: auto tokens == XLA-path tokens on the tiny model
    name = "pipeedge/test-tiny-gpt2"
    total = registry.get_model_layers(name)
    _, params, _ = registry.module_shard_factory(name, None, 1, total,
                                                 unroll=False)
    fam = registry.get_model_entry(name).family.FAMILY
    rng = np.random.default_rng(7)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 8))

    def generate():
        pipe = decode.DecodePipeline(fam, cfg, [(1, total)], [params],
                                     max_len=32, cache_bits=8)
        return np.asarray(pipe.generate(ids, 10))

    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "0")
    want = generate()
    monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "auto")
    np.testing.assert_array_equal(generate(), want)
