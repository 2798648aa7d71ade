"""Continuous batching: multi-request wave scheduling over DecodePipeline.

Interleaving S concurrent requests across K pipeline
stages must (a) stay token-identical per request to a solo generate() run
and (b) approach min(S, K)x a single stream's throughput (a solo stream
busies 1 of K stages per tick; a full wave busies all K).
"""
import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pipeedge_tpu.models import ShardConfig  # noqa: E402
from pipeedge_tpu.models import gpt2 as gpt2_mod  # noqa: E402
from pipeedge_tpu.models.layers import TransformerConfig  # noqa: E402
from pipeedge_tpu.parallel import decode  # noqa: E402
from pipeedge_tpu.parallel.batcher import ContinuousBatcher  # noqa: E402

pytestmark = pytest.mark.slow  # multi-request decode runs over a 3-stage pipeline (compile-heavy)

TINY = dict(hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
            intermediate_size=64)
PARTITION = [(1, 4), (5, 8), (9, 12)]      # 3 stages


@pytest.fixture(scope="module")
def tiny_pipe():
    from transformers import GPT2Config, GPT2LMHeadModel
    hf_cfg = GPT2Config(n_embd=32, n_layer=3, n_head=4, n_inner=64,
                        vocab_size=100, n_positions=64)
    torch.manual_seed(7)
    model = GPT2LMHeadModel(hf_cfg).eval()
    cfg = TransformerConfig(model_type="gpt2", **TINY, layer_norm_eps=1e-5,
                            vocab_size=100, max_position_embeddings=64)
    weights = {k: v.numpy() for k, v in model.state_dict().items()}
    total = 4 * cfg.num_hidden_layers
    stage_params = [gpt2_mod.load_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == total), weights)
        for l, r in PARTITION]
    return decode.DecodePipeline(gpt2_mod.FAMILY, cfg, PARTITION,
                                 stage_params, max_len=48)


def _prompts(n, batch=1, lens=(7,), seed0=11):
    rng = np.random.default_rng(seed0)
    return [np.asarray(rng.integers(0, 100, size=(batch, lens[i % len(lens)])),
                       np.int64) for i in range(n)]


def test_steady_state_throughput_3_requests_3_stages(tiny_pipe):
    """S=3 requests over K=3 stages: every stage works every steady-state
    tick, so total ticks ~= S*N (vs a solo stream's N*K ticks per request
    = S*N*K total) -> ~K x aggregate throughput."""
    S, N, K = 3, 8, len(PARTITION)
    prompts = _prompts(S)
    batcher = ContinuousBatcher(tiny_pipe)
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=N)
    results = batcher.run()

    # (a) token-identical to solo runs
    for i, ids in enumerate(prompts):
        solo = np.asarray(tiny_pipe.generate(ids, new_tokens=N))
        np.testing.assert_array_equal(results[i], solo)

    # (b) wave utilization: S*N*K stage-steps packed into ~S*N ticks
    # (+K fill/drain slack) = ~K tokens per K ticks vs solo's 1
    assert batcher.stats["stage_steps"] == S * N * K
    assert batcher.stats["tokens"] == S * N
    assert batcher.stats["ticks"] <= S * N + K
    solo_ticks_equiv = S * N * K          # a solo stream: K ticks per token
    speedup = solo_ticks_equiv / batcher.stats["ticks"]
    assert speedup >= 0.85 * min(S, K)


def test_single_request_loses_nothing_vs_solo(tiny_pipe):
    """A lone request through the batcher costs exactly N*K ticks — wave
    scheduling adds no overhead below saturation."""
    N, K = 6, len(PARTITION)
    ids = _prompts(1)[0]
    batcher = ContinuousBatcher(tiny_pipe)
    batcher.submit("solo", ids, new_tokens=N)
    results = batcher.run()
    np.testing.assert_array_equal(
        results["solo"], np.asarray(tiny_pipe.generate(ids, new_tokens=N)))
    assert batcher.stats["ticks"] == N * K


def test_paged_requests_outlive_donated_steps(tiny_pipe):
    """`--kv-pages`: every stage step gathers a request's pages into a
    cache view, hands it to a program that donates it, and scatters the
    returned view back (kv/backend.py). Two interleaved requests stay
    token-identical to their solo runs."""
    from pipeedge_tpu.kv import PagedKvBackend
    from pipeedge_tpu.telemetry import metrics as prom
    kv = PagedKvBackend(tiny_pipe, 24, 4, registry=prom.Registry())
    batcher = ContinuousBatcher(tiny_pipe, kv=kv)
    prompts = _prompts(2, lens=(7, 5))
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=6)
    results = batcher.run()
    for i, ids in enumerate(prompts):
        np.testing.assert_array_equal(
            results[i], np.asarray(tiny_pipe.generate(ids, new_tokens=6)))


def test_ready_queue_admission_and_heterogeneous_requests(tiny_pipe):
    """More requests than active slots, mixed prompt lengths and token
    budgets: completions free cache slots for pending requests; every
    result stays identical to its solo run."""
    lens = (7, 5, 9)
    prompts = _prompts(5, lens=lens)
    budgets = [4, 9, 3, 6, 5]
    batcher = ContinuousBatcher(tiny_pipe, max_active=3)
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=budgets[i])
    results = batcher.run()
    assert set(results) == set(range(5))
    for i, ids in enumerate(prompts):
        solo = np.asarray(tiny_pipe.generate(ids, new_tokens=budgets[i]))
        np.testing.assert_array_equal(results[i], solo)


def test_sampling_requests_match_solo_rng_discipline(tiny_pipe):
    """Sampled requests (temperature/top_k/seed) reproduce their solo
    generate() streams exactly: the batcher splits each request's rng
    once per picked token, like generate()."""
    prompts = _prompts(3)
    kw = [dict(temperature=0.8, top_k=0, seed=3),
          dict(temperature=0.0, top_k=0, seed=0),
          dict(temperature=1.2, top_k=5, seed=9)]
    batcher = ContinuousBatcher(tiny_pipe)
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=6, **kw[i])
    results = batcher.run()
    for i, ids in enumerate(prompts):
        solo = np.asarray(tiny_pipe.generate(ids, new_tokens=6, **kw[i]))
        np.testing.assert_array_equal(results[i], solo)


def test_batched_rows_and_validation(tiny_pipe):
    """A request may itself carry a lockstep batch; invalid submissions
    are rejected up front."""
    ids = _prompts(1, batch=4)[0]
    batcher = ContinuousBatcher(tiny_pipe)
    batcher.submit("b4", ids, new_tokens=5)
    results = batcher.run()
    np.testing.assert_array_equal(
        results["b4"], np.asarray(tiny_pipe.generate(ids, new_tokens=5)))
    assert results["b4"].shape == (4, ids.shape[1] + 5)

    with pytest.raises(ValueError, match="duplicate"):
        batcher.submit("b4", ids, new_tokens=5)  # rid already completed
    # the guard also covers ACTIVE (admitted, in-flight) requests, not
    # just pending/completed ones
    mid = ContinuousBatcher(tiny_pipe)
    mid.submit("x", ids, new_tokens=4)
    mid.tick()
    with pytest.raises(ValueError, match="duplicate"):
        mid.submit("x", ids, new_tokens=4)
    mid.run()
    with pytest.raises(ValueError, match="new_tokens"):
        batcher.submit("bad", ids, new_tokens=0)
    with pytest.raises(ValueError, match="exceeds"):
        batcher.submit("huge", ids, new_tokens=1000)
    with pytest.raises(ValueError, match="max_active"):
        ContinuousBatcher(tiny_pipe, max_active=0)


def test_eos_early_stop_frees_slot_for_pending(tiny_pipe):
    """A request with eos_token finishes the moment every row has emitted
    it — its tokens are the solo stream truncated at the first eos — and
    its freed cache slot admits a pending request."""
    prompts = _prompts(4, seed0=47)
    cap = 8
    solo0 = np.asarray(tiny_pipe.generate(prompts[0], cap))
    gen0 = solo0[0, prompts[0].shape[1]:]
    eos = int(gen0[2])                      # the 3rd greedy token
    n_stop = int(np.argmax(gen0 == eos)) + 1   # first occurrence

    batcher = ContinuousBatcher(tiny_pipe, max_active=2)
    batcher.submit(0, prompts[0], new_tokens=cap, eos_token=eos)
    for i in (1, 2, 3):
        batcher.submit(i, prompts[i], new_tokens=cap)
    results = batcher.run()

    np.testing.assert_array_equal(
        results[0], solo0[:, :prompts[0].shape[1] + n_stop])
    assert results[0][0, -1] == eos
    for i in (1, 2, 3):
        np.testing.assert_array_equal(
            results[i], np.asarray(tiny_pipe.generate(prompts[i], cap)))


def test_eos_multirow_masks_post_eos_tokens(tiny_pipe):
    """In a multi-row request, a row that hits eos early keeps decoding in
    lockstep until the whole request stops — but its post-eos tokens come
    back masked (default pad = eos; explicit pad_token honored), so
    callers never see the lockstep rows' garbage continuations."""
    rng = np.random.default_rng(53)
    ids = rng.integers(0, 100, size=(2, 4))
    cap = 8
    solo = np.asarray(tiny_pipe.generate(ids, cap))
    gen = solo[:, ids.shape[1]:]                       # [2, cap]
    # choose row 0's 2nd token as eos; make sure row 1 emits it later (or
    # never at the same step), so the request keeps running after row 0
    eos = int(gen[0, 1])
    first = [int(np.argmax(g == eos)) if eos in g else cap for g in gen]
    assert first[0] < first[1], "fixture rows stopped in the same step"

    batcher = ContinuousBatcher(tiny_pipe)
    batcher.submit("r", ids, new_tokens=cap, eos_token=eos, pad_token=77)
    out = batcher.run()["r"][:, ids.shape[1]:]
    stop = min(max(first) + 1, cap)                    # request length
    for b in range(2):
        row_stop = min(first[b] + 1, stop)
        np.testing.assert_array_equal(out[b, :row_stop], gen[b, :row_stop])
        assert (out[b, row_stop:stop] == 77).all(), (b, out[b])


def test_devices_placement_composes(tiny_pipe):
    """Stage-per-device placement (the host pipeline's deployment shape)
    composes with the batcher: results still solo-identical."""
    devices = jax.devices()
    if len(devices) < 3:
        pytest.skip("needs 3 devices")
    cfg = tiny_pipe.cfg
    placed = decode.DecodePipeline(
        gpt2_mod.FAMILY, cfg, PARTITION,
        [s["params"] for s in tiny_pipe.stages], max_len=48,
        devices=devices[:3])
    prompts = _prompts(3)
    batcher = ContinuousBatcher(placed)
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=5)
    results = batcher.run()
    for i, ids in enumerate(prompts):
        np.testing.assert_array_equal(
            results[i], np.asarray(tiny_pipe.generate(ids, new_tokens=5)))


def test_prefix_cached_requests_match_solo_and_full(tiny_pipe):
    """Prompt caching in the batcher: requests seeded from one shared
    prefix handle produce the same tokens as (a) a solo prefix-seeded
    generate and (b) a solo FULL-prompt generate, while interleaving
    with a plain (non-prefix) request."""
    rng = np.random.default_rng(29)
    prefix = rng.integers(0, 100, size=(1, 6))
    handle = tiny_pipe.precompute_prefix(prefix)
    suffixes = [rng.integers(0, 100, size=(1, 4)) for _ in range(2)]
    plain = rng.integers(0, 100, size=(1, 7))

    batcher = ContinuousBatcher(tiny_pipe)
    for i, suf in enumerate(suffixes):
        batcher.submit(i, suf, new_tokens=6, prefix=handle)
    batcher.submit("plain", plain, new_tokens=6)
    batcher.submit("sampled", suffixes[0], new_tokens=5, temperature=0.9,
                   seed=4, prefix=handle)
    results = batcher.run()

    for i, suf in enumerate(suffixes):
        want_solo = np.asarray(tiny_pipe.generate(suf, 6, prefix=handle))
        np.testing.assert_array_equal(results[i], want_solo)
        full = np.concatenate([prefix, suf], axis=1)
        want_full = np.asarray(tiny_pipe.generate(full, 6))
        np.testing.assert_array_equal(results[i], want_full[:, 6:])
    np.testing.assert_array_equal(
        results["plain"], np.asarray(tiny_pipe.generate(plain, 6)))
    np.testing.assert_array_equal(
        results["sampled"],
        np.asarray(tiny_pipe.generate(suffixes[0], 5, temperature=0.9,
                                      seed=4, prefix=handle)))


def test_prefix_handle_validated_at_submit(tiny_pipe):
    """A prefix handle built by an INCOMPATIBLE pipeline (different
    max_len here) is rejected up front with the two signatures named —
    not deep inside jit as an opaque shape error (round-4 advice). Same
    check guards solo generate(prefix=)."""
    rng = np.random.default_rng(31)
    prefix = rng.integers(0, 100, size=(1, 6))
    suffix = rng.integers(0, 100, size=(1, 4))
    handle = tiny_pipe.precompute_prefix(prefix)

    # strip the stamp -> rejected as not-a-handle
    batcher = ContinuousBatcher(tiny_pipe)
    bad = {k: v for k, v in handle.items() if k != "sig"}
    with pytest.raises(ValueError, match="precompute_prefix handle"):
        batcher.submit(0, suffix, new_tokens=4, prefix=bad)

    # forge an incompatible signature -> rejected with both sigs shown
    sig = list(handle["sig"])
    sig[2] = handle["sig"][2] + 16       # max_len field
    forged = dict(handle, sig=tuple(sig))
    with pytest.raises(ValueError, match="incompatible pipeline"):
        batcher.submit(1, suffix, new_tokens=4, prefix=forged)
    with pytest.raises(ValueError, match="incompatible pipeline"):
        tiny_pipe.generate(suffix, 4, prefix=forged)

    # the genuine handle still passes end-to-end
    batcher.submit(2, suffix, new_tokens=4, prefix=handle)
    results = batcher.run()
    np.testing.assert_array_equal(
        results[2], np.asarray(tiny_pipe.generate(suffix, 4, prefix=handle)))


def test_thread_driven_executor_matches_solo(tiny_pipe):
    """The executor on its own worker thread (the served way) is token-
    identical to solo generate() for mixed plain/sampled/prefix/eos
    requests submitted concurrently, streams per-step tokens via
    on_token, and reports its stats."""
    import threading

    rng = np.random.default_rng(41)
    prefix = rng.integers(0, 100, size=(1, 6))
    handle = tiny_pipe.precompute_prefix(prefix)
    ex = ContinuousBatcher(tiny_pipe).start()
    try:
        plain = rng.integers(0, 100, size=(2, 7))
        sampled = rng.integers(0, 100, size=(1, 5))
        suffix = rng.integers(0, 100, size=(1, 4))
        streamed = []
        outs = {}

        def client(rid, ids, n, **kw):
            ex.submit(rid, ids, n, **kw)
            outs[rid] = ex.wait(rid, timeout=300)

        threads = [
            threading.Thread(target=client, args=("plain", plain, 6),
                             kwargs={"on_token": lambda s, t:
                                     streamed.append((s, np.asarray(t)))}),
            threading.Thread(target=client, args=("sampled", sampled, 5),
                             kwargs={"temperature": 0.8, "seed": 3}),
            threading.Thread(target=client, args=("pfx", suffix, 5),
                             kwargs={"prefix": handle}),
            threading.Thread(target=client, args=("eos", plain, 6),
                             kwargs={"eos_token": 11}),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()

        np.testing.assert_array_equal(
            outs["plain"], np.asarray(tiny_pipe.generate(plain, 6)))
        np.testing.assert_array_equal(
            outs["sampled"], np.asarray(tiny_pipe.generate(
                sampled, 5, temperature=0.8, seed=3)))
        np.testing.assert_array_equal(
            outs["pfx"], np.asarray(tiny_pipe.generate(suffix, 5,
                                                       prefix=handle)))
        want_eos = ContinuousBatcher(tiny_pipe)
        want_eos.submit("eos", plain, 6, eos_token=11)
        np.testing.assert_array_equal(outs["eos"], want_eos.run()["eos"])

        # the stream delivered every step's token in order, matching the
        # result's continuation columns
        steps = sorted(streamed, key=lambda x: x[0])
        assert [s for s, _ in steps] == list(range(6))
        got = np.stack([t for _, t in steps], axis=1)
        np.testing.assert_array_equal(got, outs["plain"][:, 7:])

        snap = ex.snapshot()
        # 6 + 5 + 5 steps and the eos request's, each through every stage
        assert snap["stage_steps"] > 16 * len(PARTITION)
        assert snap["active"] == 0 and snap["pending"] == 0
        assert snap["tokens"] >= 22

        with pytest.raises(ValueError, match="duplicate"):
            ex.submit("plain2", plain, 2)  # rid free, fine
            ex.submit("plain2", plain, 2)  # duplicate while live/result
    finally:
        ex.stop()
