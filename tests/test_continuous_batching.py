"""Continuous batching + chunked prefill (ISSUE 16): iteration-level
scheduling inside the decode executor.

The invariants that let the serving plane interleave prompt ingress
with decode steps without touching numerics:

- chunked prompt passes are TOKEN-IDENTICAL to run-to-completion
  prefill on pinned seeds (greedy, sampled, and multirow) — a chunk is
  a span at an offset, and span-at-offset already carries the exact
  softmax-zero masking argument (tests/test_kv_plane.py);
- the chunk interleave is deterministic (same workload -> same chunk
  count, same tokens);
- join/retire happen at step boundaries: `on_step` fires once per
  decode-step pick, `step_join` admits a queued request in the same
  tick a slot frees, and the prefill token budget defers prompt work
  behind waiting decode steps without ever starving it;
- expiry/cancel retire mid-prompt at a CHUNK boundary with every page
  returned to the pool;
- paged speculative decoding (draft/verify caches on the page pools)
  stays token-identical to the dense speculative path and closes its
  page accounting.
"""
import threading

import numpy as np
import pytest

from pipeedge_tpu.kv import KvPagePool, PagedKvBackend  # noqa: E402
from pipeedge_tpu.parallel.batcher import ContinuousBatcher  # noqa: E402
from pipeedge_tpu.parallel.speculative import SpeculativeDecoder  # noqa: E402
from pipeedge_tpu.telemetry import metrics as prom  # noqa: E402

MODEL = "pipeedge/test-tiny-gpt2"
PARTITION = [(1, 4), (5, 8)]
MAX_LEN = 48


def _mk_pipe(max_len=MAX_LEN, seed_perturb=None):
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    params = [registry.module_shard_factory(MODEL, None, l, r, stage=i,
                                            unroll=False)[1]
              for i, (l, r) in enumerate(PARTITION)]
    if seed_perturb is not None:
        import jax
        params = jax.tree_util.tree_map(
            lambda x: x + 0.01 * (seed_perturb % 7), params)
    return decode.DecodePipeline(
        registry.get_model_entry(MODEL).family.FAMILY,
        registry.get_model_config(MODEL), PARTITION, params,
        max_len=max_len)


@pytest.fixture(scope="module")
def pipe():
    return _mk_pipe()


def _backend(pipe, n_pages=24, page_size=4):
    return PagedKvBackend(pipe, n_pages, page_size,
                          registry=prom.Registry())


def _prompts(n, batch=1, lens=(6,), seed0=11):
    rng = np.random.default_rng(seed0)
    return [np.asarray(rng.integers(
        0, 100, size=(batch, lens[i % len(lens)])), np.int64)
        for i in range(n)]


# ---------------------------------------------------------------------------
# chunked prefill: token parity + determinism
# ---------------------------------------------------------------------------

def test_chunked_wave_token_identical_to_dense(pipe):
    """Long prompts through the chunked wave batcher (greedy, sampled,
    multirow) match solo dense generate() token for token, and every
    long prompt actually ran as chunk waves."""
    kv = _backend(pipe)
    batcher = ContinuousBatcher(pipe, kv=kv, chunk_tokens=4)
    prompts = _prompts(3, lens=(17, 23, 9))
    kwargs = [dict(), dict(temperature=0.8, seed=3),
              dict(temperature=1.1, top_k=5, seed=9)]
    for i, (ids, kw) in enumerate(zip(prompts, kwargs)):
        batcher.submit(i, ids, new_tokens=6, **kw)
    multirow = _prompts(1, batch=2, lens=(14,), seed0=29)[0]
    batcher.submit("b2", multirow, new_tokens=5)
    results = batcher.run()
    for i, (ids, kw) in enumerate(zip(prompts, kwargs)):
        solo = np.asarray(pipe.generate(ids, 6, **kw))
        np.testing.assert_array_equal(results[i], solo)
    np.testing.assert_array_equal(
        results["b2"], np.asarray(pipe.generate(multirow, 5)))
    # 17 -> 5 chunks, 23 -> 6, 9 -> 3, 14 -> 4 (the 4-token chunking of
    # every prompt longer than chunk_tokens)
    assert batcher.stats["prefill_chunks"] == 5 + 6 + 3 + 4
    # every page came back
    cached = kv.trie.stats()["pages_cached"]
    assert kv.pool.free_pages + cached == kv.pool.n_pages


def test_chunked_thread_driven_token_identical(pipe):
    """The executor on its own worker thread, fed by concurrent client
    threads: same parity contract, same chunk accounting."""
    kv = _backend(pipe)
    ex = ContinuousBatcher(pipe, kv=kv, chunk_tokens=4).start()
    try:
        prompts = _prompts(2, lens=(17, 11), seed0=13)
        outs = {}

        def client(rid, ids, **kw):
            ex.submit(rid, ids, 6, **kw)
            outs[rid] = ex.wait(rid, timeout=300)

        threads = [threading.Thread(
            target=client, args=(i, ids), daemon=True,
            kwargs={} if i == 0 else {"temperature": 0.7, "seed": 5})
            for i, ids in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
        np.testing.assert_array_equal(
            outs[0], np.asarray(pipe.generate(prompts[0], 6)))
        np.testing.assert_array_equal(
            outs[1], np.asarray(pipe.generate(prompts[1], 6,
                                              temperature=0.7, seed=5)))
        assert ex.snapshot()["prefill_chunks"] == 5 + 3
    finally:
        ex.stop()
    assert kv.pool.free_pages \
        + kv.trie.stats()["pages_cached"] == kv.pool.n_pages


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["run-to-completion", "chunked-step-join"])
def test_long_prompt_spike_mid_run_serves_every_request(pipe, chunked):
    """Steady short requests with a spike of long prompts landing while
    they decode, through either arm of the scheduler: no request errors
    or is lost, every answer is the solo answer (so the two arms agree
    token for token), chunk waves ran in the chunked arm and only there,
    and the pool ends with every page free or cached."""
    kv = _backend(pipe, n_pages=48)
    kw = dict(chunk_tokens=8, prefill_budget=16, step_join=True) \
        if chunked else {}
    ex = ContinuousBatcher(pipe, kv=kv, **kw).start()
    short = _prompts(4, lens=(6,), seed0=61)
    long_ = _prompts(3, lens=(33, 29, 37), seed0=67)
    outs, errors = {}, []

    def client(rid, ids, new_tokens):
        try:
            ex.submit(rid, ids, new_tokens)
            outs[rid] = ex.wait(rid, timeout=300)
        except Exception as exc:    # noqa: BLE001 — counted, must be none
            errors.append((rid, repr(exc)))

    try:
        threads = [threading.Thread(target=client, daemon=True,
                                    args=(f"s{i}", ids, 8))
                   for i, ids in enumerate(short)]
        for t in threads[:2]:
            t.start()
        # the spike: back to back, while the first shorts are in flight
        spike = [threading.Thread(target=client, daemon=True,
                                  args=(f"l{i}", ids, 4))
                 for i, ids in enumerate(long_)]
        for t in spike + threads[2:]:
            t.start()
        for t in threads + spike:
            t.join(timeout=300)
            assert not t.is_alive()
        chunks = ex.snapshot()["prefill_chunks"]
    finally:
        ex.stop()
    assert errors == [] and len(outs) == len(short) + len(long_)
    for i, ids in enumerate(short):
        np.testing.assert_array_equal(
            outs[f"s{i}"], np.asarray(pipe.generate(ids, 8)))
    for i, ids in enumerate(long_):
        np.testing.assert_array_equal(
            outs[f"l{i}"], np.asarray(pipe.generate(ids, 4)))
    if chunked:
        assert chunks >= 5 + 4 + 5      # 33, 29, 37 tokens in chunks of 8
    else:
        assert chunks == 0
    assert kv.pool.free_pages \
        + kv.trie.stats()["pages_cached"] == kv.pool.n_pages


def test_chunk_interleave_deterministic(pipe):
    """Two runs of the same mixed workload produce identical tokens AND
    identical chunk counts — the interleave policy is pure queue
    arithmetic, not timing (the bench-record reproducibility
    contract)."""
    def run_once():
        kv = _backend(pipe)
        b = ContinuousBatcher(pipe, kv=kv, chunk_tokens=4,
                              prefill_budget=2)
        prompts = _prompts(3, lens=(15, 5, 21), seed0=7)
        for i, ids in enumerate(prompts):
            b.submit(i, ids, new_tokens=5)
        res = b.run()
        return ([np.asarray(res[i]) for i in range(3)],
                b.stats["prefill_chunks"], b.stats["ticks"])

    toks_a, chunks_a, steps_a = run_once()
    toks_b, chunks_b, steps_b = run_once()
    assert chunks_a == chunks_b and steps_a == steps_b
    for a, b_ in zip(toks_a, toks_b):
        np.testing.assert_array_equal(a, b_)


# ---------------------------------------------------------------------------
# step boundaries: join / retire / budget
# ---------------------------------------------------------------------------

def test_on_step_fires_once_per_decode_pick(pipe):
    """`on_step` is the admission plane's step-boundary hook: it must
    fire exactly once per decode-step pick (tokens picked), never for
    chunk or prefill waves."""
    steps = []
    kv = _backend(pipe)
    b = ContinuousBatcher(pipe, kv=kv, chunk_tokens=4,
                          on_step=lambda: steps.append(1))
    prompts = _prompts(2, lens=(13, 6), seed0=19)
    for i, ids in enumerate(prompts):
        b.submit(i, ids, new_tokens=4)
    b.run()
    assert len(steps) == 2 * 4


@pytest.mark.parametrize("sampled", [True, False],
                         ids=["steps-alone", "rows-together"])
def test_step_join_admits_in_the_completion_tick(pipe, sampled):
    """With max_active=1, a queued request that steps alone (a sampled one)
    must enter stage 0 in the SAME tick its predecessor completes (the
    reversed stage drain visits stage 0 after the completion) — strictly
    fewer ticks than the wave-boundary default. The rows that step
    together leave when their tokens are read, between two steps, and a
    freed slot is taken at the next step boundary whatever `step_join`
    says: the same ticks both ways."""
    def ticks_to_drain(step_join):
        b = ContinuousBatcher(pipe, max_active=1, step_join=step_join)
        for i, ids in enumerate(_prompts(3, lens=(5,), seed0=23)):
            b.submit(i, ids, new_tokens=3,
                     temperature=0.7 if sampled else 0.0, seed=i)
        n = 0
        while b.tick():
            n += 1
        assert len(b.results) == 3
        return n

    joined, waved = ticks_to_drain(True), ticks_to_drain(False)
    if sampled:
        assert joined < waved, (joined, waved)
    else:
        assert joined == waved, (joined, waved)


def test_stage0_budget_policy_defers_and_never_starves(pipe):
    """White-box on the stage-0 pop — deficit-round-robin over prompt
    tokens: a prompt head that outruns the accrued budget is deferred
    behind the first QUEUED decode step; with budget in hand FIFO order
    resumes; with no step waiting, prompt work passes regardless
    (spending into deficit), so starvation is impossible."""
    b = ContinuousBatcher(pipe, chunk_tokens=4, prefill_budget=2)
    chunk = ("rc", np.zeros((1, 4), np.int64), "chunk")
    step = ("rs", np.zeros((1, 1), np.int64), "step")
    # budget short of the 4-token head + a step queued -> step jumps
    b._stage_q[0].extend([chunk, step])
    b._budget = 2
    assert b._pop_stage0() is step
    assert list(b._stage_q[0]) == [chunk]
    # budget covers the head -> FIFO resumes, tokens are spent
    b._stage_q[0].append(step)
    b._budget = 4
    assert b._pop_stage0() is chunk
    assert b._budget == 0
    assert b._pop_stage0() is step
    # no decode step waiting -> the prompt passes anyway, into deficit
    b._stage_q[0].append(chunk)
    b._budget = 0
    assert b._pop_stage0() is chunk
    assert b._budget == -4
    assert not b._stage_q[0]


def test_budget_runs_token_identical(pipe):
    """The budget policy reorders work; it must never change it: the
    same workload under a starved budget and the one-chunk-per-tick
    default produces identical tokens."""
    def run(budget):
        kv = _backend(pipe)
        b = ContinuousBatcher(pipe, kv=kv, chunk_tokens=4,
                              prefill_budget=budget)
        b.submit("d", _prompts(1, lens=(4,), seed0=31)[0], new_tokens=8)
        b.submit("p", _prompts(1, lens=(20,), seed0=37)[0], new_tokens=4)
        res = b.run()
        return {k: np.asarray(v) for k, v in res.items()}

    res_tight, res_loose = run(1), run(4)
    for k in res_tight:
        np.testing.assert_array_equal(res_tight[k], res_loose[k])


def test_set_chunk_tokens_is_live(pipe):
    """The brownout governor's lever: set_chunk_tokens takes effect for
    the NEXT admitted prompt (in-flight chunk trains are unaffected)."""
    kv = _backend(pipe)
    b = ContinuousBatcher(pipe, kv=kv, chunk_tokens=8)
    b.submit(0, _prompts(1, lens=(16,), seed0=41)[0], new_tokens=2)
    b.run()
    first = b.stats["prefill_chunks"]
    assert first == 2                      # 16 tokens / 8
    b.set_chunk_tokens(4)
    b.submit(1, _prompts(1, lens=(16,), seed0=43)[0], new_tokens=2)
    b.run()
    assert b.stats["prefill_chunks"] == first + 4


# ---------------------------------------------------------------------------
# chunk-boundary expiry / cancel: retire mid-prompt, zero leaks
# ---------------------------------------------------------------------------

def test_cancel_mid_chunk_retires_and_frees_pages(pipe):
    """A request cancelled while its prompt is still chunk-streaming
    retires at the next chunk boundary — bare-prompt result, every
    page back in the pool."""
    kv = _backend(pipe)
    b = ContinuousBatcher(pipe, kv=kv, chunk_tokens=4)
    cancel = threading.Event()
    ids = _prompts(1, lens=(20,), seed0=47)[0]
    b.submit("c", ids, new_tokens=6, cancel=cancel)
    assert b.tick()                        # first chunk enters flight
    cancel.set()
    while b.tick():
        pass
    # retired with the bare prompt (the serving layer's 504 shape)
    np.testing.assert_array_equal(b.results["c"], ids)
    assert b.active == 0
    assert kv.pool.free_pages \
        + kv.trie.stats()["pages_cached"] == kv.pool.n_pages


def test_deadline_expiry_mid_chunk_thread_driven(pipe):
    """Same retire point with the executor on its own worker thread,
    driven by the deadline flavor of cancellation: expired mid-prompt ->
    bare prompt back, no leaked pages, slot freed for the next request."""
    import time
    kv = _backend(pipe)
    ex = ContinuousBatcher(pipe, kv=kv, chunk_tokens=4).start()
    try:
        ids = _prompts(1, lens=(20,), seed0=53)[0]
        ex.submit("d", ids, 6, deadline=time.monotonic() + 0.001)
        out = ex.wait("d", timeout=300)
        # expiry can land before any decode pick; wherever the chunk
        # train stopped, the result is a prefix of prompt+tokens and
        # the accounting is closed
        assert out.shape[0] == 1 and out.shape[1] >= ids.shape[1]
        assert ex.active == 0
        # the slot is genuinely free: a fresh request still serves
        ex.submit("after", _prompts(1, lens=(6,), seed0=59)[0], 2)
        ex.wait("after", timeout=300)
    finally:
        ex.stop()
    assert kv.pool.free_pages \
        + kv.trie.stats()["pages_cached"] == kv.pool.n_pages


# ---------------------------------------------------------------------------
# paged speculative decoding (draft/verify caches on the page pools)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def draft_pipe():
    return _mk_pipe(seed_perturb=23)


def test_paged_speculative_token_identical(pipe, draft_pipe):
    """Speculative generation over paged caches matches BOTH the dense
    speculative path and plain greedy, on pinned seeds, for a real
    (perturbed-weights) draft and for self-draft — and both pools close
    their accounting."""
    ids = np.asarray(_prompts(1, lens=(9,), seed0=61)[0])
    want = np.asarray(pipe.generate(ids, 8))
    dense = SpeculativeDecoder(pipe, draft_pipe, gamma=3)
    np.testing.assert_array_equal(
        np.asarray(dense.generate(ids, 8)), want)

    kv = _backend(pipe)
    dpool = KvPagePool(draft_pipe, 24, 4, registry=prom.Registry())
    spec = SpeculativeDecoder(pipe, draft_pipe, gamma=3)
    spec.attach_paged(kv, dpool)
    out = np.asarray(spec.generate(ids, 8, rid="r1"))
    np.testing.assert_array_equal(out, want)
    assert spec.live_rids() == set()
    assert kv.pool.free_pages == kv.pool.n_pages
    assert dpool.free_pages == dpool.n_pages
    # self-draft accepts everything — the acceptance-path numerics
    selfspec = SpeculativeDecoder(pipe, pipe, gamma=2)
    selfspec.attach_paged(_backend(pipe),
                          KvPagePool(pipe, 24, 4,
                                     registry=prom.Registry()))
    np.testing.assert_array_equal(
        np.asarray(selfspec.generate(ids, 6)),
        np.asarray(pipe.generate(ids, 6)))
    assert selfspec.last_acceptance_rate == 1.0


def test_paged_speculative_batch_rows(pipe, draft_pipe):
    """Multirow prompts allocate per-row page tables; parity holds for
    every row."""
    ids = np.asarray(_prompts(1, batch=2, lens=(7,), seed0=67)[0])
    kv = _backend(pipe, n_pages=32)
    dpool = KvPagePool(draft_pipe, 32, 4, registry=prom.Registry())
    spec = SpeculativeDecoder(pipe, draft_pipe, gamma=2)
    spec.attach_paged(kv, dpool)
    np.testing.assert_array_equal(
        np.asarray(spec.generate(ids, 6)),
        np.asarray(pipe.generate(ids, 6)))
    assert kv.pool.free_pages == kv.pool.n_pages
    assert dpool.free_pages == dpool.n_pages


def test_paged_speculative_rejects_dense_prefix(pipe, draft_pipe):
    spec = SpeculativeDecoder(pipe, draft_pipe, gamma=2)
    spec.attach_paged(_backend(pipe),
                      KvPagePool(draft_pipe, 16, 4,
                                 registry=prom.Registry()))
    handle = spec.precompute_prefix(np.asarray([[1, 2, 3, 4]]))
    with pytest.raises(ValueError, match="paged speculative"):
        spec.generate(np.asarray([[5, 6]]), 4, prefix=handle)


def test_paged_speculative_orphan_sweep_spares_live_owners(pipe,
                                                           draft_pipe):
    """The governor-facing leak contract: pages adopted under a live
    owner survive sweeps; once the owner is gone, a simulated die-
    between-charge-and-release is reclaimed by sweep_orphans."""
    kv = _backend(pipe)
    dpool = KvPagePool(draft_pipe, 24, 4, registry=prom.Registry())
    spec = SpeculativeDecoder(pipe, draft_pipe, gamma=2)
    spec.attach_paged(kv, dpool)
    # simulate a generation that died after the page charge
    spec._live.add("dead")
    spec._alloc_paged("dead", 1, 6, 4)
    assert dpool.free_pages < dpool.n_pages
    assert spec.sweep_orphans() == 0       # owner still listed live
    spec._live.discard("dead")
    assert spec.sweep_orphans() > 0        # now reclaimed
    assert dpool.free_pages == dpool.n_pages
    # the target pool side rides the serving sweep with the same
    # liveness callable
    leaked = kv.pool.sweep_leaked(lambda: spec.live_rids())
    assert leaked > 0
    assert kv.pool.free_pages == kv.pool.n_pages
