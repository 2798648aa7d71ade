"""The rows that step together (parallel/decode_rows.py, parallel/batcher.py):
every running request's row in one program a stage, a position a row, over
a stage-wide cache of `max_active` slots. Float32 on the CPU, where a row's
tokens are exactly those of a solo `generate`."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu import telemetry
from pipeedge_tpu.parallel import decode, decode_rows
from pipeedge_tpu.parallel.batcher import M_ROWS, M_STEPS, ContinuousBatcher

MAX_LEN = 48


@pytest.fixture(scope="module")
def pipes():
    built = {}

    def get(model, stages=1):
        if (model, stages) not in built:
            partition = None if stages == 1 else [(1, 4), (5, 8)]
            built[model, stages] = decode.build_decode_pipeline(
                f"pipeedge/test-tiny-{model}", partition, max_len=MAX_LEN,
                dtype=jnp.float32)
        return built[model, stages]
    return get


def _prompts(lens, seed=7, batch=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 50, size=(batch, n)) for n in lens]


def _solo(pipe, ids, new_tokens, **kw):
    return np.asarray(pipe.generate(ids, new_tokens, **kw))


def _steps():
    return M_STEPS.value(executor="wave")


def _rows(kind):
    return M_ROWS.value(kind=kind)


@pytest.mark.parametrize("model, stages", [
    ("gpt2", 1), ("gpt2", 2), ("llama", 1), ("mistral", 1)])
def test_rows_join_and_leave_at_their_own_steps(pipes, model, stages):
    """Prompts of different lengths (different positions from the first
    step on), budgets that end at different steps, more requests than
    slots: every row's tokens are those of its solo run, and all slots are
    free afterwards. mistral: the llama block under a sliding window."""
    pipe = pipes(model, stages)
    lens, budgets = (7, 12, 3, 9, 5, 20), (9, 4, 12, 6, 1, 8)
    prompts = _prompts(lens)
    batcher = ContinuousBatcher(pipe, max_active=4)
    assert batcher.rows is not None and batcher.rows.rungs == (1, 4)
    for i, (ids, n) in enumerate(zip(prompts, budgets)):
        batcher.submit(i, ids, new_tokens=n)
    results = batcher.run()
    for i, (ids, n) in enumerate(zip(prompts, budgets)):
        np.testing.assert_array_equal(results[i], _solo(pipe, ids, n))
    assert batcher.rows.n_free == 4 and batcher.active == 0
    assert batcher.stats["tokens"] == sum(budgets)


def test_one_dispatch_steps_every_row_and_counts_them(pipes):
    """Four requests admitted together: their prompt passes go out one a
    tick, then every step is ONE `stage/exec0` span and ONE program for the
    four rows; `pipeedge_decode_step_rows_total` says how many rows a step
    carried (live) of how many its program computed (slots)."""
    pipe = pipes("gpt2")
    prompts = _prompts((6, 6, 9, 4), seed=3)
    batcher = ContinuousBatcher(pipe, max_active=8)
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=6)
    steps, live, slots = _steps(), _rows("live"), _rows("slots")
    rec = telemetry.configure()
    try:
        results = batcher.run()
    finally:
        telemetry.disable()
    for i, ids in enumerate(prompts):
        np.testing.assert_array_equal(results[i], _solo(pipe, ids, 6))
    # 4 prompt passes, then 5 steps of all four rows (the rung of 8)
    exec0 = [s for s in rec.snapshot()
             if (s["cat"], s["name"]) == ("stage", "exec0")]
    assert len(exec0) == 4 + 5 == batcher.stats["stage_steps"]
    assert sum(1 for s in exec0 if s["rid"] is None) == 5
    assert _rows("live") - live == 5 * 4
    assert _rows("slots") - slots == 5 * 8
    # a pick a prompt pass and one (inside its program) a step
    assert _steps() - steps == 4 + 5
    reads = [s for s in rec.snapshot()
             if (s["cat"], s["name"]) == ("exec", "read")]
    assert 1 <= len(reads) <= 4 + 5 + 1


def test_a_lone_request_keeps_a_one_row_step(pipes):
    """One request steps at the rung of one row whatever `max_active`
    is."""
    pipe = pipes("gpt2")
    (ids,) = _prompts((10,), seed=5)
    batcher = ContinuousBatcher(pipe, max_active=16)
    slots = _rows("slots")
    batcher.submit("a", ids, new_tokens=7)
    np.testing.assert_array_equal(batcher.run()["a"], _solo(pipe, ids, 7))
    assert _rows("slots") - slots == 6 * 1


@pytest.mark.parametrize("taken, lens, rung", [
    (5, (10,), 1),          # alone in slot 5: the rung of one row, from 5
    (3, (10, 6), 8),        # slots 3 and 4: the rung of 8, from 3
    (14, (10, 6), 8),       # slots 14 and 15: the rung of 8, from 8
    (6, (4,) * 9, 16)])     # slots 6 to 14: every slot
def test_a_rung_spans_the_live_slots_wherever_they_lie(pipes, taken, lens,
                                                       rung):
    """Requests whose slots are not the lowest (others hold those) step at
    the least rung that spans them, not at the one that reaches from slot
    0, and their tokens are their solo run's."""
    pipe = pipes("gpt2")
    prompts = _prompts(lens, seed=13)
    batcher = ContinuousBatcher(pipe, max_active=16)
    assert batcher.rows.rungs == (1, 8, 16)
    others = batcher.rows.take(taken)
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=7)
    slots, live = _rows("slots"), _rows("live")
    results = batcher.run()
    for i, ids in enumerate(prompts):
        np.testing.assert_array_equal(results[i], _solo(pipe, ids, 7))
    # a first step waits behind the prompt passes queued before it, so
    # all six steps carry every row
    assert _rows("live") - live == 6 * len(lens)
    assert _rows("slots") - slots == 6 * rung
    batcher.rows.free(others)
    assert batcher.rows.n_free == 16


@pytest.mark.parametrize("low, top, want", [
    (0, 0, (1, 0)), (5, 5, (1, 5)), (47, 47, (1, 47)), (3, 9, (8, 3)),
    (44, 47, (8, 40)), (0, 8, (32, 0)), (30, 40, (32, 16)),
    (0, 40, (48, 0)), (7, 47, (48, 0))])
def test_the_least_rung_that_spans_and_where_it_starts(pipes, low, top, want):
    rows = decode_rows.StageRows(pipes("gpt2"), 48,
                                 decode_rows.block_step_rows)
    assert rows.rungs == (1, 8, 32, 48)
    rung, base = rows.span(low, top)
    assert (rung, base) == want
    assert base <= low and top < base + rung <= 48


@pytest.mark.parametrize("stages", [1, 2])
def test_a_burst_holds_a_prompt_cache_a_stage_not_one_a_request(pipes,
                                                                stages):
    """Six requests admitted in one tick take their slots at once, but the
    cache a prompt pass fills is made as that pass goes out at stage 0 and
    given up stage by stage as its rows are installed: after any tick at
    most one a stage is alive, where admission used to make six."""
    pipe = pipes("gpt2", stages)
    prompts = _prompts((6, 9, 4, 12, 5, 7), seed=17)
    batcher = ContinuousBatcher(pipe, max_active=8)
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=5)
    reqs = list(batcher.pending)
    most, going = 0, True
    while going:
        going = batcher.tick()
        if batcher.stats["ticks"] == 1:
            assert batcher.active == 6 and batcher.rows.n_free == 2
        alive = sum(cache is not None for req in reqs
                    for cache in req.caches or ())
        assert alive <= stages - 1, alive     # stage 0's is installed
        most = max(most, alive)
    assert most == stages - 1
    for i, ids in enumerate(prompts):
        np.testing.assert_array_equal(batcher.results[i],
                                      _solo(pipe, ids, 5))


def test_a_freed_slot_is_reused_and_its_old_keys_never_attended(pipes):
    """One slot, a long request and then a short one through it: the
    second's rows are installed over the first's and it attends nothing of
    them, though the cache held the first's keys at every position the
    second later reaches."""
    pipe = pipes("gpt2")
    long_ids, short_ids = _prompts((30, 4), seed=11)
    batcher = ContinuousBatcher(pipe, max_active=1)
    batcher.submit("long", long_ids, new_tokens=12)
    batcher.submit("short", short_ids, new_tokens=20)
    results = batcher.run()
    np.testing.assert_array_equal(results["long"],
                                  _solo(pipe, long_ids, 12))
    np.testing.assert_array_equal(results["short"],
                                  _solo(pipe, short_ids, 20))


def test_a_request_of_several_rows_takes_a_slot_a_row(pipes):
    """B rows take B slots (the lowest free), step at one position, and a
    request that finds too few free waits at the head of the line."""
    pipe = pipes("gpt2")
    (pair,) = _prompts((6,), seed=13, batch=2)
    (triple,) = _prompts((8,), seed=14, batch=3)
    batcher = ContinuousBatcher(pipe, max_active=4)
    batcher.submit("pair", pair, new_tokens=5)
    batcher.submit("triple", triple, new_tokens=4)
    batcher.tick()
    assert batcher.rows.n_free == 2 and len(batcher.pending) == 1
    results = batcher.run()
    np.testing.assert_array_equal(results["pair"], _solo(pipe, pair, 5))
    np.testing.assert_array_equal(results["triple"], _solo(pipe, triple, 4))
    assert batcher.rows.n_free == 4


def test_eos_row_frees_its_slot_a_step_after_the_pick(pipes):
    """An eos request ends when its token is READ, a step after it was
    picked: the step sent meanwhile is discarded, the result is cut and
    padded as a solo run's, and the neighbour's tokens do not notice."""
    pipe = pipes("gpt2")
    ids, other = _prompts((7, 9), seed=17)
    free_run = _solo(pipe, ids, 12)[0, 7:]
    eos = int(free_run[3])              # a token its greedy stream emits
    stop = int(np.argmax(free_run == eos)) + 1
    batcher = ContinuousBatcher(pipe, max_active=2)
    seen = []
    batcher.submit("e", ids, new_tokens=12, eos_token=eos,
                   on_token=lambda step, tok: seen.append(
                       (step, np.asarray(tok).tolist())))
    batcher.submit("o", other, new_tokens=12)
    results = batcher.run()
    assert results["e"].shape == (1, 7 + stop)
    np.testing.assert_array_equal(results["e"][0, 7:], free_run[:stop])
    assert seen == [(i, [int(t)]) for i, t in enumerate(free_run[:stop])]
    np.testing.assert_array_equal(results["o"], _solo(pipe, other, 12))
    assert batcher.rows.n_free == 2


def test_expired_and_cancelled_rows_free_their_slots(pipes):
    """A deadline that passes and a cancel that is set mid-flight end
    their requests at the next tokens read, with what was decoded so far;
    the slots go to the pending request, which runs in full."""
    pipe = pipes("gpt2")
    a, b, c = _prompts((6, 8, 5), seed=19)
    cancel = threading.Event()
    batcher = ContinuousBatcher(pipe, max_active=2)

    def stop_after_three(step, tok):
        if step == 2:
            cancel.set()

    batcher.submit("cancelled", a, new_tokens=30, cancel=cancel,
                   on_token=stop_after_three)
    batcher.submit("expired", b, new_tokens=30,
                   deadline=time.monotonic() + 3600)
    batcher.submit("waiting", c, new_tokens=6)
    expiring = next(r for r in batcher.pending if r.rid == "expired")
    for _ in range(6):
        batcher.tick()
    expiring.deadline = time.monotonic() - 1      # now it has passed
    results = batcher.run()
    assert results["cancelled"].shape == (1, 6 + 3)
    np.testing.assert_array_equal(results["cancelled"],
                                  _solo(pipe, a, 3))
    got = results["expired"].shape[1] - 8
    assert 1 <= got < 30 and expiring.expired
    np.testing.assert_array_equal(results["expired"], _solo(pipe, b, got))
    np.testing.assert_array_equal(results["waiting"], _solo(pipe, c, 6))
    assert batcher.rows.n_free == 2 and batcher.active == 0


def test_a_sampled_request_steps_alone_beside_greedy_rows(pipes):
    """A sampled request keeps a cache of its own and one dispatch a step
    (its picks split its own key over its own rows), takes no slot, and
    reproduces its solo stream; the greedy rows beside it step together."""
    pipe = pipes("gpt2")
    a, b, s = _prompts((7, 5, 6), seed=23)
    batcher = ContinuousBatcher(pipe, max_active=3)
    batcher.submit("a", a, new_tokens=8)
    batcher.submit("s", s, new_tokens=8, temperature=0.8, top_k=5, seed=3)
    batcher.submit("b", b, new_tokens=8)
    live = _rows("live")
    batcher.tick()
    assert batcher.rows.n_free == 1     # two greedy rows hold slots
    results = batcher.run()
    np.testing.assert_array_equal(results["a"], _solo(pipe, a, 8))
    np.testing.assert_array_equal(results["b"], _solo(pipe, b, 8))
    np.testing.assert_array_equal(
        results["s"], _solo(pipe, s, 8, temperature=0.8, top_k=5, seed=3))
    assert _rows("live") - live == 2 * 7


@pytest.mark.parametrize("model", ["keye", "lfm2", "minicpm-sala", "moe"])
def test_a_stage_that_takes_one_pos_goes_one_request_a_dispatch(model):
    """A family whose block has no row step (keye's selection), one with a
    leaf that is a row a request (lfm2's tails) or a row every few positions
    (minicpm_sala's pooled keys) and the dense block with capacity-bound
    experts: the executor makes no stage-wide cache and every stage-step is
    one request's, its prompt pass in the family's spans where it has
    them."""
    pipe = decode.build_decode_pipeline(f"pipeedge/test-tiny-{model}",
                                        max_len=32, dtype=jnp.float32)
    assert decode_rows.rows_block_fn(pipe) is None
    prompts = _prompts((6, 9), seed=29)
    batcher = ContinuousBatcher(pipe, max_active=2)
    assert batcher.rows is None
    live = _rows("live")
    for i, ids in enumerate(prompts):
        batcher.submit(i, ids, new_tokens=4)
    results = batcher.run()
    for i, ids in enumerate(prompts):
        np.testing.assert_array_equal(results[i], _solo(pipe, ids, 4))
    spans = sum(-(-ids.shape[1] // (pipe.prefill_span or ids.shape[1]))
                for ids in prompts)
    assert batcher.stats["stage_steps"] == spans + 2 * 3
    assert _rows("live") == live


def test_the_paged_backend_keeps_its_own_dispatch(pipes):
    """`kv=`: page tables hold the cache, no slots are made."""
    from pipeedge_tpu.kv import PagedKvBackend
    from pipeedge_tpu.telemetry import metrics as prom
    pipe = pipes("gpt2")
    kv = PagedKvBackend(pipe, 24, 4, registry=prom.Registry())
    batcher = ContinuousBatcher(pipe, kv=kv)
    assert batcher.rows is None
    (ids,) = _prompts((7,), seed=31)
    batcher.submit("p", ids, new_tokens=5)
    np.testing.assert_array_equal(batcher.run()["p"], _solo(pipe, ids, 5))


def test_rows_over_a_prefix_and_over_prompt_chunks(pipes):
    """A prefix's suffix span and a chunked prompt run alone, on the
    request's own cache; its rows join the others once the prompt is
    through."""
    pipe = pipes("gpt2")
    prefix_ids, suffix, long_ids, short = _prompts((10, 5, 17, 4), seed=37)
    prefix = pipe.precompute_prefix(prefix_ids)
    batcher = ContinuousBatcher(pipe, max_active=3, chunk_tokens=6)
    batcher.submit("pre", suffix, new_tokens=7, prefix=prefix)
    batcher.submit("chunked", long_ids, new_tokens=7)
    batcher.submit("short", short, new_tokens=7)
    results = batcher.run()
    np.testing.assert_array_equal(
        results["pre"], _solo(pipe, suffix, 7, prefix=prefix))
    np.testing.assert_array_equal(results["chunked"],
                                  _solo(pipe, long_ids, 7))
    np.testing.assert_array_equal(results["short"], _solo(pipe, short, 7))
    assert batcher.stats["prefill_chunks"] == 3
    assert batcher.rows.n_free == 3


def test_served_rows_hand_host_integers_to_on_token(pipes):
    """The served way (`start()`): the worker reads a step's tokens back
    once and every request's `on_token` gets host integers, in order;
    `warm()` has built every rung before."""
    pipe = pipes("gpt2")
    prompts = _prompts((6, 8, 5), seed=41)
    batcher = ContinuousBatcher(pipe, max_active=4)
    batcher.warm()
    batcher.start()
    seen = {i: [] for i in range(3)}
    try:
        for i, ids in enumerate(prompts):
            batcher.submit(i, ids, new_tokens=9,
                           on_token=lambda step, tok, i=i:
                           seen[i].append((step, tok)))
        results = [batcher.wait(i, timeout=120) for i in range(3)]
    finally:
        batcher.stop()
    for i, ids in enumerate(prompts):
        solo = _solo(pipe, ids, 9)
        np.testing.assert_array_equal(results[i], solo)
        assert [step for step, _ in seen[i]] == list(range(9))
        assert all(isinstance(tok, np.ndarray) for _, tok in seen[i])
        assert [int(tok[0]) for _, tok in seen[i]] \
            == solo[0, ids.shape[1]:].tolist()


@pytest.mark.parametrize("base", [0, 1])
def test_walk_reads_whole_blocks_up_to_the_furthest_row(base):
    """`attend_rows` against the plain masked softmax, rows at positions on
    both sides of a block's edge, a cache that is no whole number of
    blocks, a dead row, a sliding window, from the first slot and from
    another."""
    import jax
    from pipeedge_tpu.models.layers import TransformerConfig
    from pipeedge_tpu.models.stage_cache import (LayerCache, RowsAt,
                                                 attend_rows)
    cfg = TransformerConfig(model_type="llama", hidden_size=32,
                            num_hidden_layers=1, num_attention_heads=4,
                            num_kv_heads=2, intermediate_size=64)
    rng = np.random.default_rng(0)
    rows, held, hd = 5, 44, cfg.head_dim
    k_buf, v_buf = (jnp.asarray(rng.normal(size=(2, 6, held, 2 * hd)),
                                jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(rows, 1, 4, hd)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(rows, 1, 2, hd)),
                                jnp.float32) for _ in range(2))
    pos = jnp.asarray([0, 15, 16, 43, 0], jnp.int32)    # the last: dead
    for window in (0, 9):
        ctx, bcache = jax.jit(
            lambda q, k, v: attend_rows(
                LayerCache({"k": k_buf, "v": v_buf}, 1), q, k, v,
                RowsAt(jnp.int32(base), pos, jnp.max(pos)), 16, cfg,
                window=window))(q, k_new, v_new)
        assert bcache.rows["k"].shape == (rows, 1, 2 * hd)
        for r in range(rows):
            first = max(0, int(pos[r]) - window + 1) if window else 0
            slot = base + r
            keys = jnp.concatenate([k_buf[1, slot, first:int(pos[r])]
                                    .reshape(-1, 2, hd), k_new[r]])
            values = jnp.concatenate([v_buf[1, slot, first:int(pos[r])]
                                      .reshape(-1, 2, hd), v_new[r]])
            for h in range(4):
                scores = keys[:, h // 2] @ q[r, 0, h] / np.sqrt(hd)
                want = jax.nn.softmax(scores) @ values[:, h // 2]
                np.testing.assert_allclose(
                    ctx[r, 0, h * hd:(h + 1) * hd], want, rtol=2e-5,
                    atol=2e-6)
