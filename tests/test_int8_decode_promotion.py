"""Int8-KV decode-attention promotion (ISSUE 19): the kernel opt-in is a
constructor knob on DecodePipeline (`int8_decode_attend=`), resolved once
at build — env `PIPEEDGE_INT8_DECODE_ATTEND` and the QuantizeCompute
config are fallbacks — and the production executor (ContinuousBatcher),
driven by `run()` and by its own worker thread, stays token-identical to
the XLA dequant route while the KV pages hold int8 in the KvPagePool."""
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pipeedge_tpu.kv import PagedKvBackend  # noqa: E402
from pipeedge_tpu.models import layers, registry  # noqa: E402
from pipeedge_tpu.parallel import decode  # noqa: E402
from pipeedge_tpu.parallel.batcher import ContinuousBatcher  # noqa: E402
from pipeedge_tpu.telemetry import metrics as prom  # noqa: E402

MODEL = "pipeedge/test-tiny-gpt2"
PARTITION = [(1, 4), (5, 8)]
MAX_LEN = 48


def _mk_pipe(int8_decode_attend):
    params = [registry.module_shard_factory(MODEL, None, l, r, stage=i,
                                            unroll=False)[1]
              for i, (l, r) in enumerate(PARTITION)]
    return decode.DecodePipeline(
        registry.get_model_entry(MODEL).family.FAMILY,
        registry.get_model_config(MODEL), PARTITION, params,
        max_len=MAX_LEN, cache_bits=8,
        int8_decode_attend=int8_decode_attend)


@pytest.fixture(scope="module")
def pipes():
    """(kernel-route pipe, XLA-dequant-route pipe), both int8 KV."""
    return _mk_pipe(1), _mk_pipe(0)


def _backend(pipe):
    return PagedKvBackend(pipe, 24, 4, registry=prom.Registry())


# -- opt-in resolution ---------------------------------------------------

def test_resolution_precedence(monkeypatch):
    monkeypatch.delenv("PIPEEDGE_INT8_DECODE_ATTEND", raising=False)
    prev = layers._QUANTIZE_COMPUTE
    try:
        layers.set_quantize_compute(None)
        assert decode._resolve_int8_optin(None) == 0       # all defaults
        assert decode._resolve_int8_optin(1) == 1          # explicit arg
        assert decode._resolve_int8_optin("auto") == 3
        assert decode._resolve_int8_optin("off") == 0
        # env fallback, including an explicit off
        monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "2")
        assert decode._resolve_int8_optin(None) == 2
        # the int8 compute config turns decode attend on (auto policy)
        # unless the env explicitly says otherwise
        layers.set_quantize_compute(True)
        monkeypatch.setenv("PIPEEDGE_INT8_DECODE_ATTEND", "0")
        assert decode._resolve_int8_optin(None) == 0
        monkeypatch.delenv("PIPEEDGE_INT8_DECODE_ATTEND")
        assert decode._resolve_int8_optin(None) == 3
        # constructor arg beats everything
        assert decode._resolve_int8_optin("1") == 1
    finally:
        layers.set_quantize_compute(prev)


def test_constructor_arg_binds_optin(pipes):
    pipe_kernel, pipe_xla = pipes
    assert pipe_kernel.int8_decode_optin == 1
    assert pipe_xla.int8_decode_optin == 0


# -- the executor driven both ways, token parity, int8 pages ------------

def _assert_pool_pages_int8(kv):
    for stage_leaves in kv.pool._arena:
        assert stage_leaves["k"].dtype == jnp.int8
        assert stage_leaves["v"].dtype == jnp.int8
        assert "k_scale" in stage_leaves       # dequant rows ride along


def test_wave_batcher_token_identical_with_kernel(pipes):
    pipe_kernel, pipe_xla = pipes
    kv = _backend(pipe_kernel)
    _assert_pool_pages_int8(kv)
    batcher = ContinuousBatcher(pipe_kernel, kv=kv)
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, 100, size=(1, n)) for n in (6, 9)]
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=6)
    results = batcher.run()
    for i, ids in enumerate(prompts):
        ref = np.asarray(pipe_xla.generate(ids, 6))
        np.testing.assert_array_equal(results[i], ref)
    # pages all returned (kernel path leaks no pages either)
    cached = kv.trie.stats()["pages_cached"]
    assert kv.pool.free_pages + cached == kv.pool.n_pages


def test_thread_driven_token_identical_with_kernel(pipes):
    pipe_kernel, pipe_xla = pipes
    kv = _backend(pipe_kernel)
    ex = ContinuousBatcher(pipe_kernel, kv=kv).start()
    try:
        rng = np.random.default_rng(31)
        ids = rng.integers(0, 100, size=(1, 7))
        outs = {}

        def client(rid):
            ex.submit(rid, ids, 6)
            outs[rid] = ex.wait(rid, timeout=300)

        threads = [threading.Thread(target=client, args=(f"r{i}",),
                                    daemon=True) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
        ref = np.asarray(pipe_xla.generate(ids, 6))
        for rid in outs:
            np.testing.assert_array_equal(outs[rid], ref)
    finally:
        ex.stop()
    _assert_pool_pages_int8(kv)


def test_auto_policy_route_matches_xla(pipes):
    # 'auto' resolves to the width-policy v2 kernel at these tiny widths
    # (interpret mode off-TPU) — the route serve.py takes when the int8
    # compute path is enabled. Tier-1 coverage matters here: the v2
    # lowering once broke silently on a jax rename (TPUCompilerParams)
    # because the dedicated kernel suite is slow-marked.
    _, pipe_xla = pipes
    pipe_auto = _mk_pipe("auto")
    assert pipe_auto.int8_decode_optin == 3
    rng = np.random.default_rng(47)
    ids = rng.integers(0, 100, size=(1, 8))
    np.testing.assert_array_equal(
        np.asarray(pipe_auto.generate(ids, 8)),
        np.asarray(pipe_xla.generate(ids, 8)))


def test_solo_generate_kernel_matches_xla_route(pipes):
    pipe_kernel, pipe_xla = pipes
    rng = np.random.default_rng(41)
    ids = rng.integers(0, 100, size=(1, 8))
    np.testing.assert_array_equal(
        np.asarray(pipe_kernel.generate(ids, 8)),
        np.asarray(pipe_xla.generate(ids, 8)))
