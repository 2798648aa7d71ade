"""The paged KV plane (pipeedge_tpu/kv): page-table accounting, prefix
trie, eviction under pressure, token-budget admission, KV shipping, and
the loopback disaggregated prefill/decode acceptance.

Tier-1 by design (ISSUE 14): the paged executor must stay
TOKEN-IDENTICAL to the dense-cache path on a pinned seed, and the
disaggregated split must produce the same tokens as the colocated path
— these are the gates that let the serving plane swap its memory model
without touching numerics.
"""
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp  # noqa: E402

from pipeedge_tpu.kv import (KvPagePool, PagedKvBackend,  # noqa: E402
                             PoolExhausted, PrefillFleet, PrefixTrie,
                             pages_for)
from pipeedge_tpu.kv import ship as ship_mod  # noqa: E402
from pipeedge_tpu.parallel.batcher import ContinuousBatcher  # noqa: E402
from pipeedge_tpu.parallel.decode import make_next_picker  # noqa: E402
from pipeedge_tpu.telemetry import metrics as prom  # noqa: E402
from test_serve import _await_live  # noqa: E402

MODEL = "pipeedge/test-tiny-gpt2"
PARTITION = [(1, 4), (5, 8)]
MAX_LEN = 48


def _mk_pipe(max_len=MAX_LEN):
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    params = [registry.module_shard_factory(MODEL, None, l, r, stage=i,
                                            unroll=False)[1]
              for i, (l, r) in enumerate(PARTITION)]
    return decode.DecodePipeline(
        registry.get_model_entry(MODEL).family.FAMILY,
        registry.get_model_config(MODEL), PARTITION, params,
        max_len=max_len)


@pytest.fixture(scope="module")
def pipe():
    return _mk_pipe()


def _pool(pipe, n_pages=16, page_size=4):
    return KvPagePool(pipe, n_pages, page_size,
                      registry=prom.Registry())


def _backend(pipe, n_pages=24, page_size=4, **kw):
    reg = prom.Registry()
    return PagedKvBackend(pipe, n_pages, page_size, registry=reg, **kw)


def _prompts(n, batch=1, lens=(6,), seed0=11):
    rng = np.random.default_rng(seed0)
    return [np.asarray(rng.integers(
        0, 100, size=(batch, lens[i % len(lens)])), np.int64)
        for i in range(n)]


# ---------------------------------------------------------------------------
# page pool: alloc / free / refcount
# ---------------------------------------------------------------------------

def test_pool_alloc_free_refcount(pipe):
    pool = _pool(pipe, n_pages=8, page_size=4)
    assert pool.tokens_capacity == 32
    a = pool.alloc(3)
    assert len(a) == 3 and len(set(a)) == 3
    assert pool.free_pages == 5
    # sharing adds references; release drops one at a time
    pool.share(a[:2])
    pool.release(a)                 # the original refs
    assert pool.free_pages == 6     # a[2] freed; a[0], a[1] still shared
    assert pool.refcount(a[0]) == 1
    pool.release(a[:2])
    assert pool.free_pages == 8
    assert pool.refcount(a[0]) == 0
    # over-release and foreign shares are errors, not corruption
    with pytest.raises(ValueError, match="unallocated"):
        pool.release([a[0]])
    with pytest.raises(ValueError, match="unallocated"):
        pool.share([a[0]])
    # exhaustion raises with the arithmetic in the message
    with pytest.raises(PoolExhausted):
        pool.alloc(9)
    b = pool.alloc(8)
    with pytest.raises(PoolExhausted):
        pool.alloc(1)
    pool.release(b)
    assert pages_for(0, 4) == 0 and pages_for(1, 4) == 1 \
        and pages_for(9, 4) == 3


def test_pool_gather_scatter_roundtrip(pipe):
    pool = _pool(pipe, n_pages=6, page_size=4)
    pids = pool.alloc(2)
    table = np.asarray([pids], np.int32)
    view = pool.gather(0, table)
    n_blocks = pipe.stages[0]["n_blocks"]
    cfg = pipe.cfg
    assert view["k"].shape == (n_blocks, 1, 8,
                               cfg.kv_heads * cfg.head_dim)
    # write a recognizable pattern, scatter back, re-gather
    marked = {k: jnp.full_like(v, 7.0) for k, v in view.items()}
    pool.scatter(0, table, marked, [(0, 0), (0, 1)])
    again = pool.gather(0, table)
    np.testing.assert_array_equal(np.asarray(again["k"]),
                                  np.full_like(np.asarray(view["k"]), 7.0))
    # scattering only page 0 leaves page 1 untouched
    half = {k: jnp.zeros_like(v) for k, v in again.items()}
    pool.scatter(0, table, half, [(0, 0)])
    mixed = np.asarray(pool.gather(0, table)["k"])
    assert (mixed[:, :, :4] == 0).all() and (mixed[:, :, 4:] == 7).all()
    pool.release(pids)


# ---------------------------------------------------------------------------
# prefix trie: hit / miss / partial + eviction under pressure
# ---------------------------------------------------------------------------

def test_prefix_trie_hit_miss_partial(pipe):
    pool = _pool(pipe, n_pages=16, page_size=4)
    trie = PrefixTrie(pool, registry=prom.Registry())
    toks = list(range(12))          # 3 full pages
    pids = pool.alloc(3)
    assert trie.insert(toks, pids) == 3
    assert len(trie) == 3
    # full hit: all 3 pages (caller ref taken)
    got = trie.lookup(toks)
    assert got == pids
    assert all(pool.refcount(p) == 3 for p in pids)  # alloc+trie+lookup
    # partial: first 2 pages match, third chunk differs
    part = trie.lookup(toks[:8] + [99, 98, 97, 96])
    assert part == pids[:2]
    # miss: nothing matches
    assert trie.lookup([55] * 12) == []
    # max_tokens caps the match to whole pages BELOW the limit (the
    # span-needs-a-suffix rule)
    capped = trie.lookup(toks, max_tokens=11)
    assert capped == pids[:2]
    st = trie.stats()
    assert st["lookups"] == 4 and st["pages_cached"] == 3
    for got_pids in (got, part, capped):
        pool.release(got_pids)


def test_trie_eviction_under_pressure(pipe):
    pool = _pool(pipe, n_pages=4, page_size=4)
    trie = PrefixTrie(pool, registry=prom.Registry())
    pool.set_evict_hook(trie.evict_cold)
    pids = pool.alloc(3)
    trie.insert(list(range(12)), pids)
    pool.release(pids)              # now trie-only refs: COLD
    assert trie.cold_pages() == 3 and pool.free_pages == 1
    # allocation pressure evicts cold pages (deepest/oldest leaves
    # first) instead of failing
    got = pool.alloc(3)
    assert len(got) == 3 and len(trie) < 3
    # a page still referenced by a request is NOT evictable
    pool.release(got)
    trie.evict_cold(None)           # clear phase-1 leftovers
    pids2 = pool.alloc(2)
    trie.insert(list(range(8)), pids2)
    held = trie.lookup(list(range(8)))       # live request ref
    assert held == pids2
    assert trie.cold_pages() == 0
    with pytest.raises(PoolExhausted):
        pool.alloc(4)
    pool.release(held)
    pool.release(pids2)
    assert trie.evict_cold(None) == 2        # the brownout rung's sweep
    assert pool.free_pages == 4


# ---------------------------------------------------------------------------
# token-budget admission
# ---------------------------------------------------------------------------

def test_token_budget_admission_admits_beyond_slots_worth():
    """With a token budget, many small requests are granted where the
    equivalent dense capacity would be exhausted — and a request bigger
    than the whole budget sheds immediately."""
    from pipeedge_tpu.serving import AdmissionController, AdmissionShed
    reg = prom.Registry()
    # budget = what TWO dense max_len=48 slots would hold
    ctl = AdmissionController(concurrency=32, queue_capacity=8,
                              registry=reg, token_budget=96)
    small = [ctl.admit("interactive", tokens=12) for _ in range(8)]
    assert len(small) == 8          # 8 concurrent > 2 dense slots
    snap = ctl.snapshot()
    assert snap["token_budget"] == 96 and snap["tokens_free"] == 0
    with pytest.raises(AdmissionShed) as err:
        ctl.admit("interactive", tokens=97)
    assert err.value.reason == "budget"
    # a 9th small request queues until a release returns tokens
    granted = []

    def late():
        t = ctl.admit("interactive", tokens=12)
        granted.append(t)

    th = threading.Thread(target=late, daemon=True)
    th.start()
    th.join(timeout=0.5)
    assert th.is_alive() and not granted     # parked on the budget
    ctl.release(small[0])
    th.join(timeout=30)
    assert not th.is_alive() and granted
    for t in small[1:] + granted:
        ctl.release(t)
    assert ctl.snapshot()["tokens_free"] == 96


def test_token_budget_head_keeps_queue_position():
    """A token-short EDF head is NOT re-queued behind same-deadline
    arrivals: it waits in place (peek, not pop+push) and is granted
    before later small requests once tokens free up — no starvation of
    big-context requests under sustained small-request load."""
    from pipeedge_tpu.serving import AdmissionController
    ctl = AdmissionController(concurrency=4, queue_capacity=8,
                              registry=prom.Registry(), token_budget=100)
    h1 = ctl.admit("interactive", tokens=50)
    h2 = ctl.admit("interactive", tokens=50)
    # the order is the controller's own: the tickets its grant loop
    # woke, read where it runs, under the controller's lock (the two
    # waiters both wake from ONE release, and which thread runs first
    # after that says nothing about who was granted first)
    order = []
    names = {80: "big", 10: "small"}
    grant_locked = ctl._grant_locked

    def recording_grants(now, to_wake, expired):
        before = len(to_wake)
        grant_locked(now, to_wake, expired)
        order.extend(names[t.tokens] for t in to_wake[before:])

    ctl._grant_locked = recording_grants

    def waiter(name, tokens):
        ctl.admit("interactive", tokens=tokens)

    def wait_depth(n, budget=120.0):
        end = time.monotonic() + budget
        while time.monotonic() < end and ctl.queue_depth != n:
            time.sleep(0.01)
        assert ctl.queue_depth == n

    t_big = threading.Thread(target=waiter, args=("big", 80), daemon=True)
    t_big.start()
    wait_depth(1)
    t_small = threading.Thread(target=waiter, args=("small", 10),
                               daemon=True)
    t_small.start()
    wait_depth(2)
    # 50 tokens free: not enough for the 80-token head — the small
    # request behind it must NOT overtake
    ctl.release(h1)
    t_small.join(timeout=0.5)
    assert t_small.is_alive() and not order, order
    ctl.release(h2)            # 100 free: head first, then the small
    t_big.join(timeout=30)
    t_small.join(timeout=30)
    assert order == ["big", "small"], order


@pytest.mark.parametrize("drive", ["run", "thread"])
def test_paged_submit_rejects_bigger_than_pool(pipe, drive):
    """A reservation exceeding the WHOLE pool is rejected at submit,
    however the executor is driven (waiting could never admit it; the
    pending queue would otherwise wedge behind it forever) — and so is
    a hand-passed prefix handle (rejected at submit, not as a deferred
    crash of the wave loop)."""
    ids = np.zeros((1, 6), np.int64)    # 6+8 tokens -> 4 pages > 2
    b = ContinuousBatcher(pipe, kv=_backend(pipe, n_pages=2, page_size=4))
    if drive == "thread":
        b.start()
    try:
        with pytest.raises(ValueError, match="KV page"):
            b.submit("big", ids, new_tokens=8)
        handle = pipe.precompute_prefix(np.asarray([[1, 2, 3, 4]]))
        with pytest.raises(ValueError, match="prefix trie"):
            b.submit("pfx", ids, new_tokens=2, prefix=handle)
        assert not b.pending and b.active == 0 and not b.live_rids()
        if drive == "run":
            assert b.tick() is False
    finally:
        b.stop()


def test_paged_stop_wakes_page_blocked_submitter(pipe):
    """The wake-on-stop contract extends to PAGE waits: a request kept
    pending by pool availability (slots free, pages not) must have its
    waiter raise on stop(), not hang — the paged twin of
    test_executor_stop_wakes_pending_submitter."""
    kv = _backend(pipe, n_pages=16, page_size=4)
    ex = ContinuousBatcher(pipe, kv=kv, max_active=8)
    errs = {}
    first_token = threading.Event()
    ids = np.zeros((1, 4), np.int64)

    def slow_token(step, tok):
        first_token.set()
        time.sleep(0.05)

    def client(rid, tokens, **kw):
        try:
            ex.submit(rid, ids, tokens, **kw)
            ex.wait(rid, timeout=120)
        except RuntimeError as exc:
            errs[rid] = str(exc)

    # "a" reserves 12 of the 16 pages (4+44 tokens = max_len) with a
    # generation that cannot complete between the first streamed
    # token and stop(): its on_token holds every step 50 ms. Both clients
    # submit, in order, before the worker starts
    t_a = threading.Thread(target=client, args=("a", 44), daemon=True,
                           kwargs={"on_token": slow_token})
    t_a.start()
    _await_live(ex, "a")
    t_b = threading.Thread(target=client, args=("b", 20), daemon=True)
    t_b.start()
    _await_live(ex, "b")
    ex.start()
    assert first_token.wait(timeout=120)
    # "b" (4+20 tokens -> 6 pages -> bucket 8) has a slot to take
    # (max_active=8) and 4 pages too few: it stays pending
    snap = ex.snapshot()
    assert (snap["active"], snap["pending"]) == (1, 1)
    ex.stop()
    t_a.join(timeout=120)
    t_b.join(timeout=120)
    assert not t_a.is_alive() and not t_b.is_alive(), \
        "stop() left a page-blocked submitter hanging"
    assert "in flight" in errs.get("a", "") and "b" in errs


def test_paged_batcher_active_exceeds_dense_slot_equivalent(pipe):
    """The acceptance-criteria core: on a shared-prefix workload the
    paged batcher runs MORE concurrent requests than the dense-slot
    capacity holding the same KV tokens could. Pool = 2 dense slots'
    worth of tokens (2 x max_len = 96); dense max_active for that
    memory is 2; the paged run must exceed it."""
    kv = _backend(pipe, n_pages=24, page_size=4)   # 96 tokens
    batcher = ContinuousBatcher(pipe, kv=kv)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 100, size=(1, 8))
    # seed the trie: one request runs to completion first, publishing
    # the shared prefix's pages for the concurrent burst to reuse
    batcher.submit("seed", np.concatenate(
        [shared, rng.integers(0, 100, size=(1, 4))], axis=1),
        new_tokens=4)
    batcher.run()
    for i in range(6):
        suffix = rng.integers(0, 100, size=(1, 4))
        ids = np.concatenate([shared, suffix], axis=1)
        batcher.submit(i, ids, new_tokens=4)
    peak = 0
    while batcher.tick():
        peak = max(peak, batcher.active)
    assert peak > 2, (
        f"paged admission peaked at {peak} concurrent requests; dense "
        "slots holding the same 96 KV tokens cap at 2")
    assert len(batcher.results) == 7
    # and the trie actually shared the prefix across them
    st = kv.trie.stats()
    assert st["pages_reused_total"] > 0
    assert kv.pool.free_pages + kv.trie.stats()["pages_cached"] \
        == kv.pool.n_pages


# ---------------------------------------------------------------------------
# paged decode parity (pinned seeds)
# ---------------------------------------------------------------------------

def test_paged_wave_batcher_token_identical_to_dense(pipe):
    """Greedy + sampled + eos + multirow requests through the paged
    wave batcher match solo dense generate() token for token."""
    kv = _backend(pipe)
    batcher = ContinuousBatcher(pipe, kv=kv)
    prompts = _prompts(3, lens=(6, 9, 5))
    kwargs = [dict(), dict(temperature=0.8, seed=3),
              dict(temperature=1.1, top_k=5, seed=9)]
    for i, (ids, kw) in enumerate(zip(prompts, kwargs)):
        batcher.submit(i, ids, new_tokens=6, **kw)
    multirow = _prompts(1, batch=2, seed0=29)[0]
    batcher.submit("b2", multirow, new_tokens=5)
    results = batcher.run()
    for i, (ids, kw) in enumerate(zip(prompts, kwargs)):
        solo = np.asarray(pipe.generate(ids, 6, **kw))
        np.testing.assert_array_equal(results[i], solo)
    np.testing.assert_array_equal(
        results["b2"], np.asarray(pipe.generate(multirow, 5)))
    # every page came back (no leaks across mixed request shapes)
    cached = kv.trie.stats()["pages_cached"]
    assert kv.pool.free_pages + cached == kv.pool.n_pages


def test_paged_thread_driven_token_identical_and_prefix_shared(pipe):
    """The executor on its own worker thread over pages: concurrent
    submitters, token parity, and the second same-prompt request hits
    the trie."""
    kv = _backend(pipe)
    ex = ContinuousBatcher(pipe, kv=kv).start()
    try:
        rng = np.random.default_rng(17)
        ids = rng.integers(0, 100, size=(1, 9))
        outs = {}

        def client(rid, **kw):
            ex.submit(rid, ids, 6, **kw)
            outs[rid] = ex.wait(rid, timeout=300)

        # first request publishes the prompt's pages; the concurrent
        # wave behind it shares them through the trie
        client("r0")
        threads = [threading.Thread(target=client, args=(f"r{i}",),
                                    daemon=True) for i in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
        solo = np.asarray(pipe.generate(ids, 6))
        for rid in outs:
            np.testing.assert_array_equal(outs[rid], solo)
        st = kv.trie.stats()
        assert st["pages_reused_total"] > 0, (
            "same-prompt requests never shared prefix pages")
    finally:
        ex.stop()


# ---------------------------------------------------------------------------
# KV shipping: int8 bit-path + disaggregated loopback acceptance
# ---------------------------------------------------------------------------

def test_shared_prefix_phase_hits_in_the_window_and_leaks_nothing(pipe):
    """A phase of shared-prefix traffic (loadgen's `shared:PFX:TOTAL:POOL`
    prompts, every prefix one of POOL seed-derived ones) read as the
    DIFFERENCE of two trie snapshots: warm-up misses before the window do
    not dilute it, every request after a prefix's first is a hit, every
    answer is the solo answer, and the pool closes the phase with every
    page free or cached."""
    import random

    from tools.loadgen import prompt_ids
    kv = _backend(pipe, n_pages=32, page_size=4)
    warm = ContinuousBatcher(pipe, kv=kv)
    for i, ids in enumerate(_prompts(2, lens=(9,), seed0=5)):
        warm.submit(f"w{i}", ids, new_tokens=2)     # two sure misses
    warm.run()
    before = kv.trie.stats()
    assert before["lookups"] == 2 and before["hits"] == 0

    rng = random.Random(3)
    prompts = [np.asarray([prompt_ids("shared:8:12:2", rng, base_seed=3)],
                          np.int64) for _ in range(8)]
    prefixes = {tuple(ids[0, :8]) for ids in prompts}
    assert len(prefixes) == 2                       # both pool slots drawn
    results = {}
    for i, ids in enumerate(prompts):
        # one at a time: a request's pages are published when it is
        # admitted, so the next one with its prefix finds them
        batcher = ContinuousBatcher(pipe, kv=kv)
        batcher.submit(i, ids, new_tokens=4)
        results.update(batcher.run())
    after = kv.trie.stats()
    lookups = after["lookups"] - before["lookups"]
    hits = after["hits"] - before["hits"]
    assert lookups == len(prompts)
    assert hits == len(prompts) - len(prefixes)
    assert after["pages_reused_total"] - before["pages_reused_total"] \
        >= 2 * hits                                 # 8 tokens = 2 pages
    for i, ids in enumerate(prompts):
        np.testing.assert_array_equal(
            results[i], np.asarray(pipe.generate(ids, 4)))
    assert kv.pool.free_pages + after["pages_cached"] == kv.pool.n_pages


def test_int8_kv_ship_bit_path(pipe):
    """The int8 ship path is deterministic bit-for-bit (socket bytes =
    in-memory bytes decode identically) and its dequantization error is
    bounded; bits=0 ships exactly."""
    rng = np.random.default_rng(23)
    ids = jnp.asarray(rng.integers(0, 100, size=(1, 7)), jnp.int32)
    out, caches = pipe._prefill(ids)
    logits = np.asarray(out[:, -1])
    for bits in (0, 8):
        frames = ship_mod.encode_kv_ship(caches, 7, logits, bits=bits)
        blob = ship_mod.frames_to_bytes(frames)
        via_socket = ship_mod.frames_from_bytes(
            ship_mod.ship_over_socket(blob))
        direct = ship_mod.frames_from_bytes(blob)
        h1 = ship_mod.decode_kv_ship(via_socket, pipe.dtype)
        h2 = ship_mod.decode_kv_ship(direct, pipe.dtype)
        assert h1["prompt_len"] == 7
        np.testing.assert_array_equal(h1["logits"], logits)
        for r1, r2, cache in zip(h1["stage_rows"], h2["stage_rows"],
                                 caches):
            for name in ("k", "v"):
                a, b = np.asarray(r1[name]), np.asarray(r2[name])
                np.testing.assert_array_equal(a, b)  # bit-path determinism
                ref = np.asarray(cache[name][:, :, :7])
                if bits == 0:
                    np.testing.assert_array_equal(a, ref)
                else:
                    span = ref.max() - ref.min()
                    assert np.abs(a - ref).max() <= max(
                        1e-6, float(span) / 255.0 * 2), (
                        "int8 ship error beyond the codec's step size")


def test_kv_ship_rejects_malformed(pipe):
    rng = np.random.default_rng(31)
    ids = jnp.asarray(rng.integers(0, 100, size=(1, 5)), jnp.int32)
    out, caches = pipe._prefill(ids)
    frames = ship_mod.encode_kv_ship(caches, 5, np.asarray(out[:, -1]))
    with pytest.raises(ValueError, match="magic"):
        ship_mod.decode_kv_ship(frames[1:], pipe.dtype)
    with pytest.raises(ValueError, match="bits"):
        ship_mod.encode_kv_ship(caches, 5, np.asarray(out[:, -1]),
                                bits=4)


def test_disaggregated_loopback_matches_colocated(pipe):
    """THE acceptance gate: prefill rank -> decode rank over both ship
    paths produces token streams identical to the colocated paged path
    AND to solo dense generate(), greedy and sampled, on pinned
    seeds."""
    prefill_pipe = _mk_pipe()       # the dedicated prefill fleet rank
    rng = np.random.default_rng(41)
    ids = rng.integers(0, 100, size=(1, 7))
    sampled_kw = dict(temperature=0.9, seed=6)
    for path in ("local", "wire"):
        kv = _backend(pipe)
        fleet = PrefillFleet(prefill_pipe, path=path, ship_bits=0,
                             registry=prom.Registry())
        batcher = ContinuousBatcher(pipe, kv=kv)
        batcher.submit("greedy", ids, new_tokens=6,
                       shipped=fleet.prefill(ids, rid="greedy"))
        batcher.submit("sampled", ids, new_tokens=5, **sampled_kw,
                       shipped=fleet.prefill(ids, rid="sampled"))
        results = batcher.run()
        np.testing.assert_array_equal(
            results["greedy"], np.asarray(pipe.generate(ids, 6)))
        np.testing.assert_array_equal(
            results["sampled"],
            np.asarray(pipe.generate(ids, 5, **sampled_kw)))
        # colocated paged run for the same request: identical too
        kv2 = _backend(pipe)
        colo = ContinuousBatcher(pipe, kv=kv2)
        colo.submit("greedy", ids, new_tokens=6)
        np.testing.assert_array_equal(colo.run()["greedy"],
                                      results["greedy"])


def test_pool_owner_sweep_reclaims_orphans(pipe):
    """The leak audit's mechanism: pages adopted by an owner that is no
    longer live are reclaimed by `sweep_leaked`, counted on
    pipeedge_kv_pages_leaked_total, and returned to the free list —
    while live owners and un-adopted allocations are untouched."""
    pool = _pool(pipe, n_pages=8, page_size=4)
    dead = pool.alloc(3)
    pool.adopt("dead-req", dead)
    live = pool.alloc(2)
    pool.adopt("live-req", live)
    bare = pool.alloc(1)            # raw allocation, no owner: invisible
    assert pool.free_pages == 2
    assert pool.sweep_leaked({"live-req"}) == 3
    assert pool.free_pages == 5
    assert pool.stats()["leaked"] == 3
    # idempotent: the dead owner's ledger entry is gone
    assert pool.sweep_leaked({"live-req"}) == 0
    # the live-system form: liveness as a CALLABLE, invoked AFTER the
    # ledger snapshot (a request admitted between the two reads is
    # provably live — the TOCTOU the serve governor must not hit);
    # None from the callable (snapshot raced a mutation) aborts cleanly
    assert pool.sweep_leaked(lambda: {"live-req"}) == 0
    assert pool.sweep_leaked(lambda: None) == 0
    # the racing-release contract: a disowned owner's own release path
    # sees None and does nothing (no double-release ValueError)
    assert pool.disown("dead-req") is None
    pool.release(live + bare)
    pool.disown("live-req")
    assert pool.free_pages == 8


def test_mid_ship_death_leaks_zero_pages_after_sweep(pipe):
    """Satellite acceptance (ISSUE 15): a request whose submitter dies
    mid-ship — pages charged, KV installed, nothing ever released —
    leaks ZERO pages once the orphan sweep reconciles against liveness,
    and the pool is fully usable afterwards."""
    prefill_pipe = _mk_pipe()
    kv = _backend(pipe, n_pages=24, page_size=4)
    fleet = PrefillFleet(prefill_pipe, path="local",
                         registry=prom.Registry())
    rng = np.random.default_rng(61)
    ids = rng.integers(0, 100, size=(1, 6))
    handle = fleet.prefill(ids)

    class _Req:      # the executor-side request skeleton admit needs
        rid = "died-mid-ship"
        prompt_len = 6
        new_tokens = 4
        tokens = []
        rows_done = None
        eos_token = None
        on_token = None
        pick = staticmethod(make_next_picker())

    req = _Req()
    req.ids = np.asarray(ids)
    req.shipped = handle
    import jax
    req.rng = jax.random.PRNGKey(0)
    kind, _ = kv.admit(req)
    assert kind == "step"
    taken = kv.pool.n_pages - kv.pool.free_pages
    assert taken > 0
    # the submitter dies here: no release ever runs. The sweep (liveness
    # = no live requests) must reclaim every page it held.
    leaked = kv.sweep_orphans(set())
    assert leaked == taken
    assert kv.pool.stats()["leaked"] == leaked
    # accounting closes exactly: every page is either free again or
    # legitimately retained by the TRIE (the install published the
    # prompt's full page for reuse — cached capacity, not a leak)
    cached = kv.trie.stats()["pages_cached"]
    assert kv.pool.free_pages + cached == kv.pool.n_pages
    # the request's own (late) release is a no-op, not a double-free
    kv.release(req)
    assert kv.pool.free_pages + cached == kv.pool.n_pages
    # and the pool still serves fresh requests
    batcher = ContinuousBatcher(pipe, kv=kv)
    batcher.submit("after", ids, new_tokens=4)
    np.testing.assert_array_equal(
        batcher.run()["after"], np.asarray(pipe.generate(ids, 4)))


def test_shipped_install_is_idempotent(pipe):
    """The install fence: a second `_install_shipped` for the same
    request (a retried/zombie ship delivered twice above the lease
    fence) returns the FIRST install's decision and appends no second
    token — page tables and the token stream cannot be corrupted by
    at-least-once ship delivery."""
    prefill_pipe = _mk_pipe()
    kv = _backend(pipe, n_pages=24, page_size=4, share_prefixes=False)
    fleet = PrefillFleet(prefill_pipe, path="local",
                         registry=prom.Registry())
    rng = np.random.default_rng(67)
    ids = rng.integers(0, 100, size=(1, 6))
    handle = fleet.prefill(ids)
    batcher = ContinuousBatcher(pipe, kv=kv)
    batcher.submit("idem", ids, new_tokens=4, shipped=handle)
    batcher._admit()
    req = batcher._stage_q[0][0][0]
    assert len(req.tokens) == 1        # the shipped first token
    first = req.kvstate["install_result"]
    again = kv._install_shipped(req, handle)
    assert again == first
    assert len(req.tokens) == 1, "double install double-appended tokens"
    while batcher.tick():
        pass
    np.testing.assert_array_equal(
        batcher.results["idem"], np.asarray(pipe.generate(ids, 4)))


def test_shipped_install_publishes_prefix(pipe):
    """A shipped prompt's full pages land in the decode-side trie: the
    NEXT colocated request with that prompt prefix reuses them."""
    prefill_pipe = _mk_pipe()
    kv = _backend(pipe, n_pages=24, page_size=4)
    fleet = PrefillFleet(prefill_pipe, path="local",
                         registry=prom.Registry())
    rng = np.random.default_rng(47)
    ids = rng.integers(0, 100, size=(1, 8))
    ex = ContinuousBatcher(pipe, kv=kv).start()
    try:
        ex.submit("shipped", ids, 4, shipped=fleet.prefill(ids))
        out = ex.wait("shipped", timeout=300)
        np.testing.assert_array_equal(
            out, np.asarray(pipe.generate(ids, 4)))
        assert kv.trie.stats()["pages_cached"] == 2   # 8 tokens / 4
        ex.submit("reuse", ids, 4)
        np.testing.assert_array_equal(ex.wait("reuse", timeout=300), out)
        assert kv.trie.stats()["pages_reused_total"] > 0
    finally:
        ex.stop()


# ---------------------------------------------------------------------------
# replica-to-replica prefix migration (the router drain path, ISSUE 17)
# ---------------------------------------------------------------------------

def test_export_install_prefix_roundtrip_token_identical(pipe):
    """A warm prefix exported from one backend and installed into a
    fresh one serves the SAME tokens there — the drain migration's
    correctness gate (router /kv/export -> ship codec -> /kv/import)."""
    be_a = _backend(pipe, n_pages=24, page_size=4)
    be_b = _backend(pipe, n_pages=24, page_size=4)
    bat = ContinuousBatcher(pipe, kv=be_a)
    ids = (np.arange(5, 17) % 50)[None, :]     # 12 tokens = 3 full pages
    bat.submit("warm", ids, new_tokens=4)
    out = np.asarray(bat.run()["warm"])

    toks = ids[0].tolist()
    frames, plen, pages = be_a.export_prefix(toks)
    assert plen == 12 and pages == 3
    # the export takes no lasting references: A's accounting unchanged
    assert be_a.pool.free_pages + be_a.trie.stats()["pages_cached"] \
        == be_a.pool.n_pages

    blob = ship_mod.frames_to_bytes(frames)
    handle = ship_mod.decode_kv_ship(ship_mod.frames_from_bytes(blob),
                                     pipe.dtype)
    assert be_b.install_prefix(toks, handle) == pages
    assert be_b.install_prefix(toks, handle) == 0      # idempotent
    assert be_b.pool.free_pages + be_b.trie.stats()["pages_cached"] \
        == be_b.pool.n_pages

    bat_b = ContinuousBatcher(pipe, kv=be_b)
    bat_b.submit("rerun", ids, new_tokens=4)
    out_b = np.asarray(bat_b.run()["rerun"])
    np.testing.assert_array_equal(out, out_b)
    # B really served the prompt from the migrated pages (admit's trie
    # lookup caps at prompt_len - 1, so the last full page recomputes)
    assert be_b.trie.stats()["pages_reused_total"] >= pages - 1


def test_export_unknown_prefix_returns_none(pipe):
    be = _backend(pipe, n_pages=8, page_size=4)
    assert be.export_prefix([999, 998, 997, 996]) is None


def test_install_prefix_rejects_malformed_without_leaking(pipe):
    be_a = _backend(pipe, n_pages=24, page_size=4)
    be_b = _backend(pipe, n_pages=24, page_size=4)
    bat = ContinuousBatcher(pipe, kv=be_a)
    ids = (np.arange(30, 42) % 50)[None, :]
    bat.submit("warm", ids, new_tokens=2)
    bat.run()
    toks = ids[0].tolist()
    frames, plen, pages = be_a.export_prefix(toks)
    handle = ship_mod.decode_kv_ship(
        ship_mod.frames_from_bytes(ship_mod.frames_to_bytes(frames)),
        pipe.dtype)
    free0 = be_b.pool.free_pages
    with pytest.raises(ValueError):          # stage-count mismatch
        be_b.install_prefix(toks, dict(handle,
                                       stage_rows=handle["stage_rows"][:1]))
    with pytest.raises(ValueError):          # not page-aligned
        be_b.install_prefix(toks, dict(handle, prompt_len=plen - 1))
    with pytest.raises(ValueError):          # covers more than the prefix
        be_b.install_prefix(toks[:4], handle)
    assert be_b.pool.free_pages == free0     # nothing leaked
