"""Continuous batching for pipelined decoding: concurrent requests fill the
pipeline bubbles a single autoregressive stream leaves empty.

A single stream decodes one token per FULL pipeline traversal — with K
stages, every stage idles K-1 of every K stage-times (docs/DECODE.md).
Interleaving S concurrent requests as a wave — stage i decoding request r
while stage i+1 decodes request r-1 — keeps every stage busy once S >=
K, multiplying aggregate tokens/sec by ~min(S, K) without touching the
compiled stage programs.

TPU-first constraints drive the design:

- **Static shapes preserved**: a request's prompt pass runs alone, at its
  own length, on a cache of its own, through exactly DecodePipeline's
  prefill and span programs — one program per (batch, prompt-shape)
  signature, shared by every request with that signature.
- **The rows that step together**: each stage holds ONE cache of
  `max_active` row slots, made once (`decode_rows.StageRows`). A request
  takes a slot a row at admission (lowest free first); the cache of its
  own that its prompt pass fills is made as that pass goes out at stage 0,
  and its rows are copied into the slots as the pass leaves each stage, so
  a burst admitted together holds one such cache a stage in flight, not
  one a request. From then on every row that stands at a decode step of a
  stage goes out as ONE program with a position a row, dead slots computed
  and discarded, over the least rung of rows that spans the live slots
  wherever they lie (`parallel/decode_rows.py`). The program picks
  (greedy) and keeps the next tokens on the device, so step n + 1 is
  dispatched before step n's tokens are read; the thread that ticks reads
  each step's tokens back once and hands host integers to every request's
  `on_token`. Rows join and leave between steps: a cap, every row's eos, a
  cancel or an expiry frees the slots as the tokens are read, a step after
  they were picked, and a step sent meanwhile is discarded. Token for token what a solo
  `generate()` gives, the rows being independent. Rows batch where the
  code can see that they may: the stages' programs take row positions
  (`decode_rows.rows_block_fn`: the plain dense block, llama's, and the
  window-and-full block of laguna and mellum with a ring a slot; a family
  with a leaf that is a state a request or a row every few positions has
  one `pos` a program), the cache is not paged (`kv is None`), the request
  is greedy and fits the slots. Every other request keeps a cache of its
  own per stage and one dispatch a stage-step, as all did before: a
  sampled request (its picks split its own key over its own rows), the
  paged backend's, a family's whose program takes one position.
- **A prompt pass in the family's spans**: a family that prefills in spans
  (`FamilySpec.prefill_span`) has its prompt run span by span through the
  "chunk" waves below, at its own span whatever `chunk_tokens` says, on
  the request's own cache, other requests' steps between the spans.
- **Wave scheduling, host-driven**: the scheduler advances one "tick" at a
  time; per tick each stage dispatches at most one program: one request's
  prompt pass or stage-step, or the step of every row that holds a slot.
  Stages are processed back-to-front so a request advances exactly one
  stage per tick (and a token finishing at the last stage re-enters stage
  0 within the same tick — no idle gap). JAX dispatch is asynchronous, so
  with stages placed on distinct devices the per-tick dispatches execute
  concurrently; the host never blocks inside a tick.
- **Ready-queue admission**: requests wait in a FIFO until an active slot
  frees (`max_active` bounds cache memory, default = enough to saturate
  the pipeline); arrivals and completions interleave freely mid-run —
  the "continuous" in continuous batching.
- **Iteration-level scheduling** (opt-in): `step_join=True` joins a
  pending request the moment a step boundary frees its slot (same tick,
  not next wave), and `chunk_tokens=N` splits long prompt passes into
  N-token CHUNKS interleaved with other requests' decode steps under a
  token-budget-per-step policy — a long prompt streams in at a bounded
  rate instead of monopolizing the pipeline (docs/SERVING.md).

- **One executor, two ways to drive it**: a caller that owns the loop
  submits and calls `run()` (or `tick()`) itself — tools/generate.py, the
  strict-wave tests. A server calls `start()`: the executor's own worker
  thread then ticks while there is work, handler threads `submit` and
  `wait` on the executor's condition, a worker that dies or a `stop()`
  fails every waiter instead of hanging it (tools/serve.py).

The reference has no analogue (its runtime is single-shot batch inference;
the decode subsystem itself is already beyond-reference — docs/DECODE.md).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..telemetry import metrics as prom
from ..utils.threads import make_condition
from ..models.stage_cache import STATS, read_stats
from .decode import (M_ATTEND, DecodePipeline, _repeat_batch, count_stats,
                     make_next_picker, validate_capacity)
from .decode_rows import StageRows, rows_block_fn

# iteration-level scheduling counters (docs/OBSERVABILITY.md): one family
# per event. The `executor` label has one value; it stays because scrapes
# of a running fleet spell it
M_STEPS = prom.REGISTRY.counter(
    "pipeedge_decode_steps_total",
    "decode-step boundaries crossed (one per picked token wave), "
    "by executor")
M_CHUNKS = prom.REGISTRY.counter(
    "pipeedge_prefill_chunks_total",
    "prompt chunks dispatched by the chunked-prefill scheduler, "
    "by executor")
M_ROWS = prom.REGISTRY.counter(
    "pipeedge_decode_step_rows_total",
    "rows of the decode steps that went out as one program for every "
    "running row: kind=live the rows that stood at the step, kind=slots "
    "the rows its program computed (its rung)")
M_WAKEUPS = prom.REGISTRY.counter(
    "pipeedge_wait_wakeups_total",
    "times a caller blocked in the executor's wait() woke: kind=own its "
    "request had ended (or the executor had), kind=other it had not and "
    "the caller slept again (0: a request's end wakes its own waiter)")
M_PROMPT_SPANS = prom.REGISTRY.counter(
    "pipeedge_prompt_spans_total",
    "programs a prompt pass went out as at stage 0: one for a prompt run "
    "whole, one a span where it runs in spans (a family's prefill_span, a "
    "chunked prefill); with pipeedge_prompt_positions_total, the spans a "
    "prompt and the positions a span")
M_PROMPT_POSITIONS = prom.REGISTRY.counter(
    "pipeedge_prompt_positions_total",
    "prompt positions those programs ran, a row a position")
M_STEPS.declare(executor="wave")
M_CHUNKS.declare(executor="wave")
for _kind in ("live", "slots"):
    M_ROWS.declare(kind=_kind)
for _kind in ("own", "other"):
    M_WAKEUPS.declare(kind=_kind)


class _Rows(NamedTuple):
    """One dispatch of the rows that step together, from stage 0 to the
    last: the requests whose rows stand at it, the first slot of the rung
    its program computes, and the position of each slot of the rung (-1:
    dead; both go to the device with each stage's call)."""
    reqs: list
    base: int
    pos: np.ndarray


def _sched_mark(name: str, rid) -> None:
    """Instant `sched` span (join/retire/chunk): scheduler decisions are
    point events, recorded pre-timed instead of opening a with-span."""
    if telemetry.enabled():
        now = time.monotonic_ns()
        telemetry.record("sched", name, now, now, rid=str(rid))


@dataclass
class _Request:
    rid: object
    ids: jnp.ndarray                 # [B, S] prompt (prompt included in
    new_tokens: int                  # the result; the SUFFIX when a
    pick: object                     # prefix handle seeds the caches)
    rng: jax.Array                   # the key `pick` splits next
    prompt_len: int                  # prefix + suffix
    prefix: Optional[Dict] = None    # precompute_prefix handle
    eos_token: Optional[int] = None  # stop early once every row emitted it
    pad_token: Optional[int] = None  # fills rows past their own eos
    # streaming hook: fires (step, [B] tokens) as each pick lands
    on_token: Optional[object] = None
    # the caller's handle of a stream the executor's `on_tokens` sink
    # writes (tools/serve.py): handed back with every token, never read
    stream: Optional[object] = None
    # cooperative cancellation: an is_set()-style flag (threading.Event)
    # checked after each pick — a cancelled request completes with the
    # tokens decoded so far, freeing its cache slots/admission slot early
    # (dead streaming clients must not hold capacity, tools/serve.py)
    cancel: Optional[object] = None
    # absolute monotonic deadline (docs/SERVING.md): checked at every
    # decode-step boundary; expiry FIRES the cancel flag and completes
    # the request early — expired work must stop consuming TPU time
    # mid-flight, not decode uselessly to the cap
    deadline: Optional[float] = None
    expired: bool = False            # the deadline check tripped
    rows_done: Optional[np.ndarray] = None   # [B] eos seen per row
    caches: Optional[List] = None    # per-stage caches (`_seed_caches`)
    # paged-KV plane (pipeedge_tpu/kv): page tables + sharing state when
    # a PagedKvBackend drives this request instead of dense cache slots
    kvstate: Optional[Dict] = None
    # a prefill fleet's ship handle (kv/disagg.py): the prompt pass
    # already ran remotely; admission installs the KV rows and decoding
    # starts directly at the first decode step
    shipped: Optional[Dict] = None
    # chunked prefill (docs/SERVING.md): a long prompt pass split into
    # fixed-token chunks interleaved with other requests' decode steps.
    # One chunk is in flight at a time; `chunk_rest` holds the prompt
    # tokens not yet dispatched, `chunk_off` the in-flight chunk's
    # absolute cache offset, `chunk_next` the next chunk's offset, and
    # `chunk_final` whether the in-flight chunk completes the prompt
    # (only then does the last stage pick a token / publish trie pages)
    chunk_rest: Optional[jnp.ndarray] = None
    chunk_off: int = 0
    chunk_next: int = 0
    chunk_final: bool = False
    chunks_done: int = 0
    tokens: List = field(default_factory=list)
    # the last picked token as [B, 1] ids, made by the pick's own program:
    # the next decode step's input
    step_ids: Optional[jax.Array] = None
    # the rows that step together (greedy, on a stage that takes row
    # positions): the slots of the stage-wide cache this request's rows
    # hold from admission to completion, and the tokens whose pick has been
    # DISPATCHED. `tokens` then holds host integers, appended as the worker
    # reads each step back, a step behind `sent`
    greedy: bool = True
    slots: Optional[List[int]] = None
    sent: int = 0
    done: bool = False

    @property
    def pos(self) -> int:
        """Cache position for the NEXT decode wave: the wave that produces
        token n+1 attends through position prompt_len + n - 1 (mirrors
        DecodePipeline.generate's pos), n the tokens picked so far."""
        picked = len(self.tokens) if self.slots is None else self.sent
        return self.prompt_len + picked - 1


def _build_request(pipe: DecodePipeline, rid, ids, new_tokens: int,
                   temperature: float, top_k: int, seed: int,
                   eos_token: Optional[int], pad_token: Optional[int],
                   prefix: Optional[Dict],
                   on_token=None, cancel=None,
                   deadline: Optional[float] = None,
                   shipped: Optional[Dict] = None,
                   stream=None) -> _Request:
    """Validate one request's arguments against `pipe` and build its
    `_Request` — the admission contract `submit` and tools/serve.py's
    `prevalidate` share (identical errors before and after the response
    headers commit; the rng/pick discipline of `generate`)."""
    ids = jnp.asarray(ids, jnp.int32)
    if ids.ndim != 2 or ids.shape[1] == 0:
        raise ValueError("prompt must be [B, S] with S >= 1, got "
                         f"shape {ids.shape}")
    if new_tokens < 1:
        raise ValueError(f"new_tokens must be >= 1, got {new_tokens}")
    if pad_token is not None and eos_token is None:
        raise ValueError("pad_token only applies with eos_token (rows "
                         "are padded after their own eos)")
    if prefix is not None:
        # reject handles built by an incompatible pipeline up front
        # (a mismatch would otherwise surface as an opaque jit shape
        # error mid-tick, or corrupt attend windows)
        pipe.check_prefix(prefix)
    if shipped is not None and prefix is not None:
        raise ValueError("shipped KV already covers the whole prompt; "
                         "it does not compose with a prefix handle")
    prompt_len = ids.shape[1] + (prefix["len"] if prefix else 0)
    validate_capacity(pipe.cfg, pipe.max_len, prompt_len, new_tokens)
    return _Request(
        rid=rid, ids=ids, new_tokens=new_tokens,
        pick=make_next_picker(temperature, top_k),
        rng=jax.random.PRNGKey(seed), prompt_len=prompt_len,
        prefix=prefix, eos_token=eos_token,
        pad_token=eos_token if pad_token is None else pad_token,
        on_token=on_token, stream=stream, cancel=cancel,
        deadline=None if deadline is None else float(deadline),
        shipped=shipped, greedy=temperature <= 0.0)


def _seed_caches(pipe: DecodePipeline, req: _Request) -> None:
    """`exec/seed`: create the request's per-stage caches, as its prompt
    pass goes out at stage 0 and not at admission: a request that holds
    slots gives each up again as its prompt leaves that stage
    (`StageRows.install`), so of a burst admitted together only the few
    whose prompt is on its way hold one (100 MB a row at gpt2-medium's
    1,024 positions), not all of them."""
    with telemetry.span("exec", "seed", rid=str(req.rid)):
        if req.prefix is not None:
            req.caches = [_repeat_batch(c, req.ids.shape[0])
                          for c in req.prefix["caches"]]
        else:
            req.caches = pipe._fresh_caches(req.ids.shape[0])


def _next_chunk(req: _Request, chunk_tokens: int) -> jnp.ndarray:
    """Pop the next prompt chunk off `req.chunk_rest`: advances
    `chunk_off`/`chunk_next`, sets `chunk_final` on the last slice.
    `chunk_tokens` is read per pop, so a brownout chunk clamp
    (`set_chunk_tokens`) takes effect at the next chunk boundary."""
    rest = req.chunk_rest
    take = rest.shape[1] if chunk_tokens < 1 \
        else min(int(chunk_tokens), rest.shape[1])
    req.chunk_off = req.chunk_next
    req.chunk_next += take
    data, rest = rest[:, :take], rest[:, take:]
    req.chunk_rest = rest if rest.shape[1] else None
    req.chunk_final = req.chunk_rest is None
    req.chunks_done += 1
    _sched_mark("chunk", req.rid)
    return data


def _maybe_chunk(req: _Request, kind: str, data,
                 chunk_tokens: int, always: bool = False):
    """Convert a long prompt pass into its first CHUNK. A prompt pass
    ("prefill" for a fresh prompt, "span" for a prefix/trie-seeded
    suffix) longer than `chunk_tokens` becomes a sequence of "chunk"
    waves: each runs `chunk_tokens` prompt positions as a span at its
    absolute offset (DecodePipeline.extend's rule — token-identical to
    the single pass for fp caches, where masked positions contribute
    exact softmax zeros), and the scheduler interleaves other requests'
    decode steps between chunks. The base offset is uniform across
    seeding paths: prompt_len - data_len (0 fresh, shared_len trie,
    prefix_len dense prefix). `always`: a prompt that fits one chunk is one
    chunk all the same (a family that prefills in spans has no program for
    a prompt run whole through its rings)."""
    if chunk_tokens < 1 or kind not in ("prefill", "span") \
            or (data.shape[1] <= chunk_tokens and not always):
        return kind, data
    req.chunk_next = req.prompt_len - data.shape[1]
    req.chunk_rest = data
    return "chunk", _next_chunk(req, chunk_tokens)


def _run_stage(pipe: DecodePipeline, i: int, req: _Request, data,
               kind: str):
    """One stage-step dispatch for request `req` at stage `i` — THE
    per-stage semantics (device placement, prefill vs span vs step) of
    dense cache slots (kv/backend.py's `run_stage` is the paged twin).
    Each step records a
    request-tagged `stage`/`exec{i}` span (rid = the request id), so
    trace_report --request attributes a slow request's per-stage compute
    without a fleet trace — free when span recording is off. The mb tag
    stays None: decode-step indices are NOT microbatch ids, and tagging
    them as such would cross-link unrelated concurrent requests through
    every mb-keyed consumer (trace_slice, flow events)."""
    st = pipe.stages[i]
    with telemetry.span("stage", f"exec{i}", stage=i,
                        rid=str(req.rid)):
        if st["device"] is not None:
            data = jax.device_put(data, st["device"])
        if kind == "prefill":
            out, req.caches[i] = st["prefill"](st["params"], data,
                                               req.caches[i])
        elif kind == "span":
            # prefix-seeded prompt pass: the suffix runs as one span at
            # the prefix offset (DecodePipeline.extend's rule)
            out, req.caches[i] = pipe._decode_step(
                st, data, req.caches[i], req.prefix["len"],
                span=data.shape[1])
        elif kind == "chunk":
            # chunked prefill: this slice of the prompt runs as a span
            # at its absolute offset; earlier chunks' KV rows are
            # already in the caches, so attention is exact. A family's
            # own span gives its last row alone to the head, as
            # `DecodePipeline._prefill`'s does
            out, req.caches[i] = pipe._decode_step(
                st, data, req.caches[i], req.chunk_off,
                span=data.shape[1],
                last_only=bool(pipe.prefill_span)
                and i + 1 == len(pipe.stages))
        else:
            out, req.caches[i] = pipe._decode_step(st, data, req.caches[i],
                                                   req.pos)
    return out


def _expired(req: _Request, now: Optional[float] = None) -> bool:
    """THE deadline check, at every decode-step and chunk boundary and
    at admission: past-deadline requests fire the
    existing `cancel` flag — one cancellation mechanism, two triggers
    (client disconnect, deadline) — and record `expired` so the serving
    layer can tell a 504 from an ordinary early completion."""
    if req.deadline is None:
        return False
    if (now if now is not None else time.monotonic()) < req.deadline:
        return False
    req.expired = True
    cancel_set = getattr(req.cancel, "set", None)
    if cancel_set is not None:
        cancel_set()
    return True


def _finalize_tokens(req: _Request) -> np.ndarray:
    """[B, S + T] result array: prompt + picked tokens, with everything
    strictly after each row's first eos masked to its pad token (rows
    that hit eos early kept decoding in lockstep; no garbage
    continuation reaches the caller)."""
    if not req.tokens:
        # a request expired/cancelled before its first pick completes
        # with the bare prompt (the serving layer answers it 504)
        return np.asarray(req.ids)
    toks = np.stack([np.asarray(t) for t in req.tokens], axis=1)  # [B, T]
    if req.eos_token is not None:
        seen = np.cumsum(toks == req.eos_token, axis=1) > 0
        after = np.concatenate(
            [np.zeros_like(seen[:, :1]), seen[:, :-1]], axis=1)
        toks = np.where(after, req.pad_token, toks)
    return np.concatenate([np.asarray(req.ids), toks], axis=1)


# -- the finish phases ----------------------------------------------------
# Each is one `exec` span (docs/OBSERVABILITY.md): with `stage`/`exec{i}`
# and the worker's `exec`/`wait0` they keep the executor's thread inside a
# named span for all of its time, so an idle gap of the device is named
# by the phase of the executor it fell in.

def _pick_token(req: _Request, out):
    """`exec/pick`: one program (decode.make_next_picker) splits the
    request's rng, picks the next token from the last position's logits
    (prefill [B,S], span [B,S_s], step [B,1]) and shapes it as the next
    step's input — the split-per-pick rng discipline of generate()."""
    with telemetry.span("exec", "pick", rid=str(req.rid)):
        token, req.step_ids, req.rng = req.pick(out, req.rng)
        req.tokens.append(token)
        M_STEPS.inc(executor="wave")
    return token


def _all_rows_eos(req: _Request, token) -> bool:
    """`exec/eos`: read the just-picked token back (blocks on the device)
    and report whether every row of the request has now emitted eos."""
    with telemetry.span("exec", "eos", rid=str(req.rid)):
        hit = np.asarray(token) == req.eos_token
        req.rows_done = hit if req.rows_done is None \
            else req.rows_done | hit
        return bool(req.rows_done.all())


class ContinuousBatcher:
    """Wave-scheduled multi-request decoding over a `DecodePipeline`.

    >>> batcher = ContinuousBatcher(pipe)
    >>> batcher.submit("a", ids_a, new_tokens=8)
    >>> batcher.submit("b", ids_b, new_tokens=5, temperature=0.7, seed=1)
    >>> results = batcher.run()      # {"a": [B, S_a+8], "b": [B, S_b+5]}

    Results are token-identical to `pipe.generate(ids, new_tokens, ...)`
    run solo with the same sampling settings: the same compiled stage
    programs run on the same per-request data; only the interleaving
    differs. `stats` afterwards reports ticks/stage_steps/tokens — in
    steady state with >= n_stages active requests every stage works every
    tick, i.e. ~1 token per tick vs a solo stream's 1 per n_stages.

    Served, the executor drives itself: `start()` runs the ticks on its
    own worker thread, and each caller thread (one HTTP handler a request
    in tools/serve.py) hands a request over and blocks for its result, on
    an event that this request's end alone sets:

    >>> batcher = ContinuousBatcher(pipe, max_active=48).start()
    >>> batcher.submit("a", ids, new_tokens=8)   # returns immediately
    >>> out = batcher.wait("a")                  # [B, S+8]
    >>> batcher.stop()

    `max_active` bounds the requests that run, and is the count of row
    slots of each stage's cache (a request of B rows takes B); the rest
    wait in `pending`. A worker that raises marks the executor dead, and
    `stop()` with requests in flight does the same: every current and
    later `submit` and `wait` raises instead of hanging (the /healthz
    contract of tools/serve.py).
    """

    def __init__(self, pipe: DecodePipeline, max_active: Optional[int] = None,
                 kv=None, chunk_tokens: int = 0,
                 prefill_budget: Optional[int] = None,
                 step_join: bool = False, on_step=None, on_tokens=None):
        if pipe.sp_degree != 1:
            raise ValueError("continuous batching drives per-request decode "
                             "waves; sp prefill is a whole-pipeline pass "
                             "(prefill each request solo instead)")
        self.pipe = pipe
        self.n_stages = len(pipe.stages)
        # paged-KV backend (kv/backend.py): when set, requests hold page
        # tables over the shared pool instead of private dense slots, and
        # admission is bounded by PAGES (max_active defaults to the pool's
        # page count — effectively token-bounded concurrency)
        self.kv = kv
        if max_active is None:
            max_active = (self.n_stages + 1 if kv is None
                          else max(self.n_stages + 1, kv.pool.n_pages))
        self.max_active = max_active
        if self.max_active < 1:
            raise ValueError(f"max_active must be >= 1, got {self.max_active}")
        # chunked prefill (docs/SERVING.md): prompt passes longer than
        # `chunk_tokens` are split into chunk waves; `prefill_budget`
        # bounds the prompt tokens ENTERING stage 0 per tick (default:
        # one chunk's worth), so decode steps keep landing while a long
        # prompt streams in. 0 disables chunking.
        if chunk_tokens < 0:
            raise ValueError(f"chunk_tokens must be >= 0, got {chunk_tokens}")
        self.chunk_tokens = int(chunk_tokens)
        # a family that prefills in spans (`FamilySpec.prefill_span`: its
        # rings hold a window, its mixers run in chunks) has its prompt
        # pass run span by span through the same "chunk" waves, on the
        # request's own cache, other requests' steps between the spans:
        # no option, the family says, and its span is the chunk whatever
        # `chunk_tokens` is
        self.prefill_budget = (self.chunk_tokens if prefill_budget is None
                               else int(prefill_budget))
        if self.chunk_tokens and self.prefill_budget < 1:
            raise ValueError("prefill_budget must be >= 1 when chunking")
        self._budget = 0
        # step_join: refill a slot freed at the LAST stage into stage 0
        # within the SAME tick (the reversed drain visits stage 0 after
        # the completion), so admission happens at step boundaries, not
        # wave boundaries. Off by default: strict-wave timing is the
        # contract tests/test_batcher.py pins.
        self.step_join = bool(step_join)
        # on_step(): fired after each decode-step boundary (a pick
        # landed) — tools/serve.py chains admission re-grants to it
        self.on_step = on_step
        # on_tokens(rows): the hand-over of a tick's tokens, ONE call for
        # every request submitted with a `stream`: rows is a list of
        # (stream, step, [B] tokens), in the order the rows stand in the
        # step. Called under the executor's lock: it must not block
        # (tools/serve.py appends the list to its writer's queue)
        self.on_tokens = on_tokens
        self.pending: deque = deque()
        self.active = 0
        self._live_rids = set()      # pending + admitted (not yet completed)
        # stage i's input queue: (request, data, kind) tuples with kind in
        # {"prefill", "span", "chunk", "step"} ("span" = a prefix-seeded
        # request's suffix prompt pass, "chunk" = one slice of a chunked
        # prompt pass); `data` is token ids at stage 0, the previous
        # stage's hidden state after
        self._stage_q: List[deque] = [deque() for _ in range(self.n_stages)]
        self.results: Dict = {}
        self.stats = {"ticks": 0, "stage_steps": 0, "tokens": 0,
                      "prefill_chunks": 0}
        # the rows that step together (module docstring): a stage-wide
        # cache of `max_active` row slots, where the stages' programs take
        # a position a row and no paged backend holds the cache
        block_fn = rows_block_fn(pipe) if kv is None else None
        self.rows: Optional[StageRows] = None if block_fn is None \
            else StageRows(pipe, self.max_active, block_fn)
        # the requests whose newest token this tick put into `rows.ids`,
        # and the ticks not read back yet as (that tick's ids, its requests)
        self._sent: List[_Request] = []
        self._unread: deque = deque()
        # the device's counts already in the registry (`count_stats`)
        self._counted = 0
        # the served life cycle (start/wait/stop): ONE condition guards
        # the queues and `results` between the worker and the caller
        # threads, and the worker alone waits on it (for work). A caller
        # that finds its request unfinished leaves an event of its own in
        # `_waiters`, which that request's end sets (`_hand_back`).
        # tools/serve.py takes the same lock for its prefix registry and
        # its admission checks: there is no second lock to order against.
        # Re-entrant, so a caller may hold it across a look-up of its own
        # and `submit`.
        self.cond = make_condition("batcher.results")
        self._waiters: Dict = {}
        self._worker: Optional[threading.Thread] = None
        self._stop = False
        self._dead: Optional[BaseException] = None

    @property
    def _chunk(self) -> int:
        """Positions a prompt pass runs at a time; 0 = whole."""
        return self.pipe.prefill_span or self.chunk_tokens

    def set_chunk_tokens(self, n: int) -> None:
        """Retarget the chunk size (GIL-atomic int write) — the brownout
        ladder's chunk-clamp rung calls this from the governor thread;
        in-flight requests see it at their next chunk boundary."""
        self.chunk_tokens = max(0, int(n))

    def submit(self, rid, ids, new_tokens: int, temperature: float = 0.0,
               top_k: int = 0, seed: int = 0,
               eos_token: Optional[int] = None,
               pad_token: Optional[int] = None,
               prefix: Optional[Dict] = None,
               on_token=None, cancel=None,
               deadline: Optional[float] = None,
               shipped: Optional[Dict] = None, stream=None) -> None:
        """Queue a request. `ids` [B, S] is a prompt batch decoded in
        lockstep (B=1 for a single sequence); each distinct (B, S) shape
        compiles its own prefill program, shared across requests.

        `shipped` (with a paged-KV backend only) is a prefill fleet's ship
        handle (kv/disagg.py): the prompt pass already ran remotely, so
        admission installs the KV rows into this request's pages and
        decoding starts at the first decode step.

        `prefix` (from the pipeline's `precompute_prefix`) seeds this
        request's cache slots with a shared prompt prefix; `ids` is then
        the request's SUFFIX, its prompt pass runs as one span at the
        prefix offset, and — matching `generate`'s prefix contract — the
        returned array omits the prefix. Many queued requests can share
        one handle: that is the point (1 prefix prefill for the fleet).

        `eos_token`: finish this request early — freeing its cache slots
        for the ready queue — once EVERY row of its batch has emitted the
        token (`new_tokens` stays the hard cap). Rows that finished first
        keep DECODING until the whole request stops, but their post-eos
        tokens are masked with `pad_token` (default: the eos token, HF
        generate's pad-after-eos convention) in the returned array, so
        callers never consume a finished row's garbage continuation. The
        continuous-batching payoff: short answers release capacity
        immediately instead of padding to the cap.

        `on_token(step, tokens)` fires once a token of every row, in
        order: a library caller's streaming hook. `tokens` is `[B]`: host
        integers where the request's rows step with the others (the
        executor has read them back), the device array as its pick lands
        where the request steps alone (the callback decides when to block
        on the read-back). `np.asarray` takes either. `stream` is the
        server's way: any handle, given back as `(stream, step, tokens)`
        in the ONE call a tick of the executor's `on_tokens` that carries
        every streaming request's token (`tools/serve.py` chains it to
        chunked HTTP responses).

        `cancel` (an is_set()-style flag, e.g. threading.Event) requests
        cooperative cancellation: once set, the request completes at its
        next pick with the tokens decoded so far — freeing its cache
        slots for pending requests instead of decoding to the cap for a
        caller that stopped listening.

        `deadline` (absolute `time.monotonic()` seconds) bounds the
        request's USEFUL lifetime: the executor checks it at every
        decode-step boundary, and expiry fires the `cancel` flag and
        completes the request with the tokens decoded so far
        (`docs/SERVING.md` — expired work must not keep consuming the
        pipeline)."""
        with self.cond:
            self._check_dead()
            if rid in self.results or rid in self._live_rids:
                raise ValueError(f"duplicate request id {rid!r}")
            if shipped is not None and self.kv is None:
                raise ValueError("shipped KV needs a paged-KV backend "
                                 "(ContinuousBatcher(kv=...))")
            req = _build_request(self.pipe, rid, ids, new_tokens,
                                 temperature, top_k, seed, eos_token,
                                 pad_token, prefix, on_token=on_token,
                                 cancel=cancel, deadline=deadline,
                                 shipped=shipped, stream=stream)
            if self.kv is not None:
                # a reservation bigger than the whole pool would wedge the
                # pending queue forever (can_admit never true): reject it
                # up front like the dense path's capacity check
                self.kv.check_admittable(req)
            self._live_rids.add(rid)
            self.pending.append(req)
            self.cond.notify()      # the worker alone waits here, for work

    def _admit(self) -> None:
        """Join pending requests while slots (and pages) last: `exec/admit`,
        on the thread that ticks."""
        if not self.pending or self.active >= self.max_active:
            return
        with telemetry.span("exec", "admit"):
            self._admit_pending()

    def _admit_pending(self) -> None:
        while self.pending and self.active < self.max_active:
            req = self.pending[0]
            if _expired(req):
                # dead before its first wave: never seed caches or touch
                # the pipeline — the whole point of deadline propagation
                self.pending.popleft()
                self._hand_back(req)
                continue
            if self.kv is not None:
                if not self.kv.can_admit(req):
                    break       # head-of-line: wait for page releases
                self.pending.popleft()
                kind, data = self.kv.admit(req)
                if req.tokens:
                    # shipped install picked the first token in admit
                    self.stats["tokens"] += int(req.ids.shape[0])
                    self._emit([(req, req.tokens[-1])])
                if kind == "done":
                    self.kv.release(req)
                    self._hand_back(req)
                    continue
            else:
                n_rows = int(req.ids.shape[0])
                if (self.rows is not None and req.greedy
                        and n_rows <= self.rows.slots):
                    if self.rows.n_free < n_rows:
                        break       # head-of-line: wait for rows to leave
                    req.slots = self.rows.take(n_rows)
                self.pending.popleft()
                # a prefix-seeded request's suffix runs as one SPAN at the
                # prefix offset (prompt caching); otherwise a fresh prefill
                kind = "prefill" if req.prefix is None else "span"
                data = req.ids
            kind, data = _maybe_chunk(req, kind, data, self._chunk,
                                      always=bool(self.pipe.prefill_span))
            if kind == "chunk":
                self.stats["prefill_chunks"] += 1
                M_CHUNKS.inc(executor="wave")
            self.active += 1
            _sched_mark("join", req.rid)
            self._stage_q[0].append((req, data, kind))

    def _finish_wave(self, req: _Request, out, kind: str,
                     reentries: list, eos_pending: list) -> None:
        """Last stage done: pick the next token, then complete or re-enter
        stage 0 (same split-per-pick rng discipline as generate()).

        Requests with an eos_token defer their stop decision to AFTER the
        tick's dispatch loop (`eos_pending`): the decision needs a host
        readback of the token, and blocking here — the loop's first
        iteration — would serialize every other stage's dispatch behind
        this request's compute.

        An INTERMEDIATE prompt chunk produces no token: its chunk
        boundary is a scheduling point — retire an expired/cancelled
        request right here (its pages/slots free without decoding a
        single token) or queue the next chunk."""
        if kind == "chunk" and not req.chunk_final:
            if _expired(req) or (req.cancel is not None
                                 and req.cancel.is_set()):
                self._complete(req)   # mid-prompt shed: free pages now
                return
            data = _next_chunk(req, self._chunk)
            self.stats["prefill_chunks"] += 1
            M_CHUNKS.inc(executor="wave")
            reentries.append((req, data, "chunk"))
            return
        if req.slots is not None:
            self._join_rows(req, out, reentries)
            return
        token = _pick_token(req, out)
        self.stats["tokens"] += int(token.shape[0])
        self._emit([(req, token)], self.on_step)
        done = len(req.tokens) >= req.new_tokens
        if not done and (_expired(req) or (req.cancel is not None
                                           and req.cancel.is_set())):
            self._complete(req)     # expired/caller gone: free the slots
            return
        if req.eos_token is not None:
            eos_pending.append(req)
            return
        if done:
            self._complete(req)
        else:
            reentries.append((req, req.step_ids, "step"))

    def _emit(self, landed: list, on_step=None) -> None:
        """`exec/emit`: the hand-over of the tokens that landed, `(request,
        [B] tokens)` each and each already the last of its `req.tokens`:
        the executor's `on_step` (tools/serve.py: admission re-grants), a
        request's own `on_token`, and ONE call of `on_tokens` for every
        row of a stream, whatever their number: one span, one call and
        one wake-up of the writer behind it a tick."""
        with telemetry.span("exec", "emit"):
            if on_step is not None:
                on_step()
            rows = []
            for req, token in landed:
                step = len(req.tokens) - 1
                if req.on_token is not None:
                    req.on_token(step, token)
                if req.stream is not None:
                    rows.append((req.stream, step, token))
            if rows and self.on_tokens is not None:
                self.on_tokens(rows)

    def _hand_back(self, req: _Request) -> None:
        """A request's end: its result where `wait` finds it, and its own
        waiter woken, nobody else's."""
        self.results[req.rid] = _finalize_tokens(req)
        self._live_rids.discard(req.rid)
        waiter = self._waiters.pop(req.rid, None)
        if waiter is not None:
            waiter.set()

    def _complete(self, req: _Request) -> None:
        with telemetry.span("exec", "retire", rid=str(req.rid)):
            self._hand_back(req)
            req.caches = None            # free this request's cache slots
            req.chunk_rest = None
            req.done = True
            if req.slots is not None:
                self.rows.free(req.slots)    # ... its rows of the stages'
            if self.kv is not None:
                self.kv.release(req)     # ... or its page references
            self.active -= 1
            _sched_mark("retire", req.rid)
        if self.step_join:
            # the slot freed at THIS step boundary joins a pending
            # request into stage 0 immediately: the reversed drain has
            # not reached stage 0 yet, so the joiner's first wave
            # dispatches within the same tick (iteration-level
            # scheduling, not wave-level)
            self._admit()

    def _decide_eos(self, req: _Request) -> None:
        """Post-dispatch stop decision for an eos request: read back the
        just-picked token (all of this tick's work is already dispatched,
        so the fence overlaps other requests' device compute)."""
        token = req.tokens[-1]
        done = len(req.tokens) >= req.new_tokens
        if not done:
            done = _all_rows_eos(req, token)
        if done:
            self._complete(req)
        else:
            self._stage_q[0].append((req, req.step_ids, "step"))

    def _pop_stage0(self):
        """Token-budget-per-step policy at stage 0: the budget accrues
        `prefill_budget` tokens per tick (capped so it cannot bank an
        unbounded prompt burst) and prompt-kind dispatches
        (prefill/span/chunk) spend it. A prompt head that outruns the
        accrued budget is deferred behind the first queued decode step —
        decode steps keep landing at a guaranteed rate while a long
        prompt streams in at `prefill_budget` tokens/tick. When no
        decode step is waiting, prompt work passes regardless (budget
        throttles competition, not progress), so starvation is
        impossible. Pure deterministic queue arithmetic: interleaving is
        reproducible under a pinned seed."""
        q = self._stage_q[0]
        if self.chunk_tokens and q[0][2] != "step" \
                and q[0][1].shape[1] > self._budget:
            for k in range(1, len(q)):
                if q[k][2] == "step":
                    q.rotate(-k)
                    item = q.popleft()
                    q.rotate(k)   # restore order minus item k
                    return item
        item = q.popleft()
        if item[2] != "step":
            self._budget -= item[1].shape[1]
        return item

    # -- the rows that step together ---------------------------------------

    def _join_rows(self, req: _Request, out, reentries: list) -> None:
        """A request that holds slots leaves its prompt pass: its first
        token is picked as any request's is and put into its slots of the
        stage-wide ids, where its first step finds it and the worker's next
        read-back brings it to the host."""
        with telemetry.span("exec", "pick", rid=str(req.rid)):
            _, step_ids, req.rng = req.pick(out, req.rng)
            self.rows.join(step_ids, req.slots)
            M_STEPS.inc(executor="wave")
        self._sent_token(req, reentries)

    def _sent_token(self, req: _Request, reentries: list) -> None:
        """The pick of `req`'s next token has gone out: it re-enters stage 0
        unless that was its last. An eos, a cancel or an expiry shows a
        step later, when the token is read (`_deliver`), and the step sent
        meanwhile is computed and discarded."""
        req.sent += 1
        self.stats["tokens"] += len(req.slots)
        self._sent.append(req)
        if req.sent < req.new_tokens:
            reentries.append((req, None, "step"))

    def _gather_rows(self, first: _Request) -> "_Rows":
        """Every request queued for a step at stage 0 whose rows hold slots,
        `first` (just popped) among them, as ONE dispatch: the rung that
        spans their slots, lowest to highest, and each row's position, dead
        (-1) where a slot of the rung is not theirs."""
        def rows_step(item):
            return item[2] == "step" and item[0].slots is not None

        q = self._stage_q[0]
        reqs = [first] + [item[0] for item in q if rows_step(item)]
        if len(reqs) > 1:
            self._stage_q[0] = deque(item for item in q
                                     if not rows_step(item))
        held = [slot for req in reqs for slot in req.slots]
        rung, base = self.rows.span(min(held), max(held))
        pos = np.full(rung, -1, np.int32)
        for req in reqs:
            pos[np.asarray(req.slots) - base] = req.pos
        return _Rows(reqs, base, pos)

    def _step_rows(self, i: int, group, hidden, reentries: list):
        """One stage-step of every row that stands at it: one program, ONE
        `stage`/`exec{i}` span. `group` is the `_Rows` the stage before
        handed on, or at stage 0 the request just popped, whose step takes
        every other queued one with it. At the last stage the program has
        picked: the counters, the `on_step` hook and the rows' re-entry
        follow. -> (the group, the stage's output)."""
        t0 = time.monotonic_ns()
        last = i + 1 == self.n_stages
        with telemetry.span("stage", f"exec{i}", stage=i):
            if i == 0:
                group = self._gather_rows(group)
            out = self.rows.step(i, hidden, group.base, group.pos)
            live, rung = group.pos[group.pos >= 0], len(group.pos)
            M_ATTEND.inc(rung * self.rows.walked(
                rung, int(group.pos.max())), phase="decode", kind="read")
            M_ATTEND.inc(int(live.sum()), phase="decode", kind="live")
            if last:
                M_STEPS.inc(executor="wave")
                M_ROWS.inc(live.size, kind="live")
                M_ROWS.inc(rung, kind="slots")
        if telemetry.enabled():
            # the dispatch is every row's: `trace_report --request` finds
            # a request's share of a stage under `compute/rows{i}`
            t1 = time.monotonic_ns()
            for req in group.reqs:
                telemetry.record("compute", f"rows{i}", t0, t1, stage=i,
                                 rid=str(req.rid))
        if last:
            if self.on_step is not None:
                self.on_step()
            for req in group.reqs:
                if not req.done:
                    self._sent_token(req, reentries)
        return group, out

    def _read(self, due: list) -> list:
        """`exec/read`: the one read-back a step. Blocks on the device for
        the tokens of the ticks in `due`; touches nothing the caller threads
        share, so the worker is outside its lock here."""
        if not due:
            return due
        with telemetry.span("exec", "read"):
            return [(np.asarray(ids)[:, 0], reqs) for ids, reqs in due]

    def _deliver(self, read: list) -> None:
        """Hand the tokens a read-back brought over, as host integers and
        a tick's in one `_emit`, and decide each request's end from them:
        the cap, every row's eos, a cancel or an expiry. Rows leave here,
        between steps, and pending requests take their slots for the next
        tick."""
        for host, reqs in read:
            landed = []
            for req in reqs:
                if req.done:
                    continue        # ended a step ago: computed, discarded
                token = host[req.slots]
                req.tokens.append(token)
                landed.append((req, token))
            if landed:
                self._emit(landed)  # a cancel set in there shows below
            for req, token in landed:
                done = len(req.tokens) >= req.new_tokens
                if not done and (_expired(req) or (
                        req.cancel is not None and req.cancel.is_set())):
                    done = True     # expired/caller gone: free the slots
                elif not done and req.eos_token is not None:
                    done = _all_rows_eos(req, token)
                if done:
                    self._complete(req)
        if read:
            self._admit()

    def _due(self, fresh: bool) -> list:
        """The ticks to read back now: every one but this tick's own, which
        waits until the next tick's programs are out (step n + 1 is
        dispatched before step n's tokens are read)."""
        keep = 1 if fresh else 0
        return [self._unread.popleft()
                for _ in range(len(self._unread) - keep)]

    def count_stats(self) -> None:
        """Add what the family's block steps have counted on the device
        since the last call (`FamilySpec.stats_names`; nothing where it has
        none) to the registry's `pipeedge_<name>_total{phase}`, as
        `DecodePipeline.generate` does once a batch: phase=prefill what the
        installed requests' prompt passes counted, phase=decode what the
        steps of the rows that step together did. The read waits for the
        step in flight (under the executor's lock, so no step donates the
        leaf meanwhile): a scrape of `/metrics` calls it, no tick does. A
        request that steps alone (sampled) is not counted."""
        names = getattr(self.pipe.family, "stats_names", ())
        if self.rows is None or not names or not self.rows.prompt_stats:
            return
        with self.cond:
            prompt = sum(read_stats({STATS: stats})
                         for stats in self.rows.prompt_stats)
            steps = sum(read_stats(cache) for cache in self.rows.caches)
            now = np.stack([prompt, steps])
            gained, self._counted = now - self._counted, now
        count_stats(names, *gained)

    def warm(self) -> None:
        """Build the programs that no request of a warm-up sent alone would
        meet (the wider rungs of the rows' step) before traffic comes: a
        compile inside a live window is a stalled user."""
        if self.rows is not None:
            with self.cond:
                self.rows.warm()

    def tick(self) -> bool:
        """Advance every stage by at most one stage-step; returns whether
        any work remains.

        Strict wave semantics: stages are drained back-to-front and a
        token finishing at the last stage re-enters stage 0 only AFTER the
        tick, so every request advances exactly one stage per tick and a
        tick dispatches at most one program a stage. That makes a
        tick one parallel stage-time: no intra-tick data dependencies, so
        with stages on distinct devices the asynchronously dispatched
        steps genuinely overlap. (A solo request therefore costs exactly
        n_stages ticks per token — the pipeline-bubble baseline the
        batcher exists to fill.) With `step_join`, completions refill
        stage 0 mid-tick; with `chunk_tokens`, stage 0's pop obeys the
        per-tick prefill token budget. A step popped at stage 0 takes
        every other queued step of rows that hold slots with it: they are
        one dispatch, and stay one through the later stages.

        The tokens of the rows that step together are read back a tick
        late, after the next tick's programs are out."""
        worked, fresh = self._dispatch()
        self._deliver(self._read(self._due(fresh)))
        return (worked or self.active > 0 or bool(self.pending)
                or bool(self._unread))

    def _dispatch(self):
        """A tick's programs. -> (whether any went out, whether rows that
        step together were sent a token, which `_unread` now holds)."""
        cap = max(self.prefill_budget, self.chunk_tokens)
        self._budget = min(self._budget + self.prefill_budget, cap)
        self._admit()
        worked = False
        reentries: list = []
        eos_pending: list = []
        for i in reversed(range(self.n_stages)):
            q = self._stage_q[i]
            if i == 0 and any(item[0].done for item in q):
                # ended (eos, cancel, expiry) while their next step waited
                q = self._stage_q[0] = deque(
                    item for item in q if not item[0].done)
            if not q:
                continue
            req, data, kind = (self._pop_stage0() if i == 0
                               else q.popleft())
            self.stats["stage_steps"] += 1
            worked = True
            if kind == "rows" or (kind == "step"
                                  and req.slots is not None):
                group, out = self._step_rows(i, req, data, reentries)
                if i + 1 < self.n_stages:
                    self._stage_q[i + 1].append((group, out, "rows"))
                continue
            if i == 0:
                M_PROMPT_SPANS.inc()
                M_PROMPT_POSITIONS.inc(data.shape[0] * data.shape[1])
            if self.kv is not None:
                out = self.kv.run_stage(i, req, data, kind)
            else:
                if req.caches is None:
                    _seed_caches(self.pipe, req)    # its prompt sets out
                out = _run_stage(self.pipe, i, req, data, kind)
            if req.slots is not None and (kind != "chunk"
                                          or req.chunk_final):
                # the prompt is through this stage: its rows go into
                # their slots of the stage's cache
                with telemetry.span("exec", "install", rid=str(req.rid)):
                    self.rows.install(i, req.caches[i], req.slots)
                    req.caches[i] = None
            if i + 1 < self.n_stages:
                self._stage_q[i + 1].append((req, out, kind))
            else:
                self._finish_wave(req, out, kind, reentries, eos_pending)
        self._stage_q[0].extend(reentries)
        for req in eos_pending:
            self._decide_eos(req)
        self.stats["ticks"] += worked
        self._admit()                # a completion may free a slot mid-tick
        fresh = bool(self._sent)
        if fresh:
            self._unread.append((self.rows.ids, self._sent))
            self._sent = []
        return worked, fresh

    def run(self) -> Dict:
        """Drive ticks until every submitted request completes; returns
        {rid: [B, prompt+new_tokens] ids} (prompt included)."""
        while self.tick():
            pass
        return self.results

    # -- the served life cycle: the executor's own worker thread ----------

    def start(self) -> "ContinuousBatcher":
        """Start the worker thread that ticks while there is work. Only a
        caller that wants `submit`/`wait` from other threads asks for it;
        `run()` and `tick()` drive the same executor without one."""
        if self._worker is None:
            self._worker = threading.Thread(target=self._loop, daemon=True,
                                            name="decode-executor")
            self._worker.start()
        return self

    def _loop(self) -> None:
        while True:
            # `exec/wait0`, the worker's only wait for the caller threads:
            # first for the condition's lock, which every submitting
            # caller thread shares with it, then for work
            with telemetry.span("exec", "wait0", stage=0):
                self.cond.acquire()
                while not self._stop and not (self.pending or self.active
                                              or self._unread):
                    self.cond.wait()
            try:
                # a tick in three parts: its programs go out under the
                # lock; the read-back of the tick before, which blocks on
                # the device, runs outside it (callers `submit` and `wait`
                # meanwhile); the tokens are handed out under it again
                try:
                    if self._stop:
                        return
                    _, fresh = self._dispatch()
                    due = self._due(fresh)
                finally:
                    self.cond.release()
                read = self._read(due)
                with self.cond:
                    self._deliver(read)
            except BaseException as exc:   # noqa: BLE001 — a wedged
                # worker would hang every waiter forever; record the
                # failure so they raise instead
                self._die(exc)
                raise

    @property
    def dead(self) -> Optional[BaseException]:
        """What killed the worker, or the `stop()` that ended it; None
        while the executor serves."""
        return self._dead

    def _die(self, exc: BaseException) -> None:
        """Record the first cause of death and wake every waiter, and the
        worker where it waits for work."""
        with self.cond:
            if self._dead is None:
                self._dead = exc
            for waiter in self._waiters.values():
                waiter.set()
            self._waiters.clear()
            self.cond.notify_all()

    def _check_dead(self) -> None:
        if self._dead is not None:
            raise RuntimeError(f"serving worker died: {self._dead!r}")

    def wait(self, rid, timeout: Optional[float] = None) -> np.ndarray:
        """Block until request `rid` completes and take its [B, S + T] ids
        (the array `run()` would record). Raises RuntimeError once the
        worker has died or `stop()` cut the request short, TimeoutError
        after `timeout` seconds."""
        end = None if timeout is None else time.monotonic() + timeout
        while True:
            with self.cond:
                if rid in self.results:
                    return self.results.pop(rid)
                self._check_dead()
                # this request's own event: its end sets it (`_hand_back`),
                # and the executor's (`_die`); no other request's does
                waiter = self._waiters.setdefault(rid, threading.Event())
            left = None if end is None else end - time.monotonic()
            if (left is not None and left <= 0) or not waiter.wait(left):
                self._waiters.pop(rid, None)
                raise TimeoutError(f"request {rid!r} not done after "
                                   f"{timeout}s")
            own = rid in self.results or self._dead is not None
            M_WAKEUPS.inc(kind="own" if own else "other")

    def live_rids(self) -> Optional[set]:
        """The ids of every request pending or admitted and not yet
        complete: the liveness set of tools/serve.py's orphan sweep. Read
        without the lock (a sweep must not wait out a tick); None when
        the copy raced a mutation three times, and the next sweep retries."""
        for _ in range(3):
            try:
                return set(self._live_rids)
            except RuntimeError:     # set mutated during copy
                continue
        return None

    def snapshot(self) -> Dict:
        """The stats /healthz shows. Lock-free and best-effort (GIL-atomic
        reads; a momentary inconsistency is fine for health)."""
        return dict(self.stats, active=self.active,
                    pending=len(self.pending))

    def stop(self) -> None:
        """Stop the worker at the next tick boundary. A request still
        pending or in flight can never finish then, so its waiter is
        FAILED rather than left hanging, and so is every later `submit`.
        Drain with `wait` before stopping if results matter."""
        # set before the lock is asked for: a worker with work re-takes
        # its lock at once, and reads this at its next tick boundary
        self._stop = True
        self._die(RuntimeError(f"executor stopped with "
                               f"{len(self._live_rids)} request(s) in flight"))
        if self._worker is not None:
            self._worker.join()
