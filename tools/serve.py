"""HTTP serving front end over the pipelined decode executor.

Beyond-reference serving surface (the reference runtime is single-shot
batch inference; SURVEY.md §2.4): a stdlib-only JSON/HTTP server over one
`ContinuousBatcher` (parallel/batcher.py), whose own worker thread ticks
the waves — strict wave semantics, JAX async dispatch keeps every stage
busy from a single host thread. Handler threads submit to it and wait on
it, each for its own request's end: requests admit as they arrive, share
the pipeline, and prompt prefixes registered once via /prefix are reused
by any number of /generate requests (prompt caching). A streamed answer's
lines are written by ONE writer thread (`pipeedge_tpu/serving/streams.py`),
to which the executor hands a tick's tokens in one call: a request is one
thread, its handler, whatever the number of its tokens (docs/SERVING.md,
"The threads of a server").

Overload is handled as a fault, not a steady state (docs/SERVING.md):
every /generate rides the SLO-aware admission plane
(`pipeedge_tpu/serving/`) — per-class token buckets, a bounded
earliest-deadline-first queue, and watermark-driven brownout — so a
surge shed excess load with 503 + a Retry-After computed from the
observed service rate instead of degrading every request. Requests may
carry `"class"` ("interactive" | "batch" | "best_effort", default
interactive) and `"deadline_ms"` (budget from receipt); the deadline
propagates into the executor, which cancels expired work at the next
decode-step boundary (HTTP 504, `pipeedge_deadline_exceeded_total`).

Endpoints (all JSON unless noted):
- GET  /healthz            -> {"ok", "model", "stages", "speculative",
                               "executor", "degraded": false | {"dead_rank",
                               "since_s", "retry_after"},
                               "serving": {deadline_exceeded_total,
                               "admission": {queue_depth, in_flight,
                               shed_classes, service_rate_rps, ...},
                               "brownout": {level, name, floor, ...}},
                               "peer_health": {rank: {state, score,
                               windows}} (the gray-failure scorer's
                               per-peer view when one runs here, {}
                               otherwise — docs/FAULT_TOLERANCE.md),
                               "stats": {tokens, active,
                               pending, prefixes,
                               degraded_entered_total,
                               failover_replays_total,
                               rejoined_ranks_total, last_dead_rank, ...}};
                               the degraded object carries a "phase"
                               ("degraded" | "healing");
                               HTTP 503 once a serving worker has died
- GET  /metrics            -> Prometheus text format (the observability
                              plane, docs/OBSERVABILITY.md): request count/
                              latency histogram, tokens served, per-edge
                              activation wire-byte counters, degraded/
                              failover counters — plus every monitoring
                              key's (instant|window|global) matrix as
                              gauges when a monitoring session is open,
                              and whatever the runtime's DCN hooks fed
                              into the shared registry (wire bytes,
                              negotiated edge bitwidths, heartbeats),
                              the span recorder's cumulative digest
                              (pipeedge_span_{seconds,count}_total) and
                              the compile counters
- POST /debug/profile?seconds=N
                           -> {"path": DIR, "seconds": n} — with
                              --profile-dir DIR only: one JAX profiler
                              session of N seconds (capped) into DIR, the
                              serving loop's spans on the device trace's
                              clock; 409 while a session is live
- POST /degraded {"degraded": bool, "dead_rank"?: n, "retry_after"?: s,
                  "healing"?: bool, "healed"?: bool, "rank"?: n}
                           -> {"degraded": bool} — the failover
                              orchestrator's hook: while degraded, new
                              work is answered 503 + Retry-After and
                              /healthz names the dead rank; an in-flight
                              request whose executor fails during the
                              window is replayed once after recovery.
                              Lifecycle (docs/FAULT_TOLERANCE.md): the
                              orchestrator posts {"degraded": true, ...}
                              at the death, {"degraded": true, "healing":
                              true} once the rank rejoins (window still
                              open, /healthz phase flips to "healing"),
                              and {"degraded": false, "healed": true,
                              "rank": n} when capacity is restored — that
                              last form clears the window AND counts the
                              rank on pipeedge_serve_rejoined_ranks_total
- POST /debug/dump {"rid"?: "q17"}
                           -> {"path": ..., "written_total": n} — write a
                              flight-recorder postmortem bundle NOW
                              (docs/OBSERVABILITY.md): the event ring, a
                              request-scoped span slice, and the
                              admission/brownout state. Bundles are also
                              written automatically on 504s, sheds,
                              degraded windows, and SLO-breach brownout
                              steps; /healthz's "flight" block names the
                              latest bundle path.
- POST /prefix   {"ids": [t0, t1, ...]}
                           -> {"prefix_id": "p0", "len": N}
- POST /generate {"ids": [[...], ...] | [...], "new_tokens": N,
                  "temperature"?: f, "top_k"?: n, "seed"?: n,
                  "eos_token"?: n, "prefix_id"?: "p0",
                  "stream"?: true, "speculative"?: true}
                           -> {"ids": [[prompt+continuation], ...],
                               "rid": "q17"}
                              (suffix+continuation when prefix_id given;
                              "rid" is the minted request id — the trace
                              key for `trace_report --request`, also
                              carried by 503/504 error bodies)

With `"stream": true` the response is chunked `application/x-ndjson`:
one line per decode step `{"step": i, "tokens": [[...]]}` as the token
lands (raw picked tokens — post-eos rows are NOT yet masked), then a
final line `{"ids": ..., "first_token_ms": t, "steps": n}` carrying the
authoritative (eos-masked) result, identical to the non-streaming
response. First-token latency is measured server-side from request
receipt to the first line's write.

Paged KV plane (`--kv-pages N`, docs/SERVING.md): the executor swaps
its dense per-request cache slots for page tables over one shared
pool (pipeedge_tpu/kv/) — admission charges a KV TOKEN budget
(prompt + max-new-tokens pages) instead of max_active slots, prompt
prefixes are shared across requests automatically through a token-hash
trie (/prefix then registers only the token list), and the brownout
ladder gains an evict-cold-pages rung. `--disaggregate local|wire`
additionally splits serving into a prefill fleet (a dedicated pipeline
running only prompt passes) and the decode executor, shipping finished
KV pages over the wire-v2 codec (`--kv-ship-bits 8` for int8 wire
bytes) — token streams stay identical to colocated serving. /healthz
gains a `serving.kv` block (pool/prefix snapshots).

Speculative requests (`"speculative": true`, needs --draft-model) run
greedy draft/verify rounds under a DEDICATED lock: they serialize with
each other (bounding draft+verify cache memory at one in-flight
speculative generation) but NOT with plain requests or result waits —
JAX dispatch is thread-safe, so the batcher keeps serving while a
speculative generation runs (round-4 advice).

Tokens are identical to solo `DecodePipeline.generate` runs with the
same settings — the executor's contract (tests/test_serve.py).

Usage: python tools/serve.py -m gpt2 [--port 8321] ...
"""
import argparse
import json
import os
import sys
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pipeedge_tpu import health as peer_health  # noqa: E402
from pipeedge_tpu import telemetry  # noqa: E402
from pipeedge_tpu.serving.streams import Stream, StreamWriter  # noqa: E402
from pipeedge_tpu.serving import (AdmissionController,  # noqa: E402
                                  AdmissionShed, BrownoutLadder,
                                  DeadlineExceeded, REQUEST_CLASSES,
                                  Watermarks, default_policies,
                                  parse_class_map)
from pipeedge_tpu.telemetry import collector as fleet_obs  # noqa: E402
from pipeedge_tpu.telemetry import flight  # noqa: E402
from pipeedge_tpu.telemetry import metrics as prom  # noqa: E402
from pipeedge_tpu.utils.threads import make_lock  # noqa: E402

# request outcomes the per-class counter tracks (the request-class x
# outcome matrix — pre-declared at service construction, pipelint PL501)
REQUEST_OUTCOMES = ("ok", "shed", "deadline", "degraded", "error")

# hop-propagation header (serving/router.py mints the fleet-level rid
# and carries it here; a replica mints its own `q<n>` only when the
# header is absent — direct, unrouted requests)
RID_HEADER = "X-PipeEdge-Rid"


def _header_rid(headers) -> Optional[str]:
    """A sane caller-supplied rid from the request headers, else None
    (it lands in span rings, logs, and postmortem filenames — bound
    and sanitize it)."""
    raw = headers.get(RID_HEADER)
    if not raw:
        return None
    rid = raw.strip()
    if not rid or len(rid) > 128 or not rid.isprintable():
        return None
    return rid


def _rid_headers(rid) -> tuple:
    """Response-header echo of the request id (ops cross-reference a
    client complaint to a bundle without body parsing)."""
    return ((RID_HEADER, rid),) if rid else ()


class ServiceDegraded(RuntimeError):
    """The service is in a failover window (a backing stage died): new
    work should come back later instead of queueing into the hole."""

    def __init__(self, dead_rank, retry_after: float):
        where = f" (rank {dead_rank} dead)" if dead_rank is not None else ""
        super().__init__(
            f"service degraded during failover{where}; retry after "
            f"{retry_after:g}s")
        self.dead_rank = dead_rank
        self.retry_after = retry_after


class _Service:
    """Owns the pipeline, the executor and the writer of the streams; HTTP
    handler threads submit requests and wait for their results."""

    def __init__(self, pipe, max_active=None, max_prefixes=8, spec=None,
                 edge_itemsize=2,
                 admission_enabled=True, queue_capacity=64,
                 class_rates=None, class_deadlines_s=None,
                 brownout_enabled=True, brownout_marks=None,
                 clamp_new_tokens=16, governor_interval=0.25,
                 postmortem_dir=None, kv_pages=0, kv_page_size=16,
                 prefill_fleet=None, prefill_supervisor=None,
                 chunked_prefill=0, step_join=False,
                 prefill_budget=None, clamp_chunk_tokens=0,
                 slo_objective=0.99, slo_burn_fast=30.0,
                 slo_burn_slow=300.0, slo_burn_threshold=10.0):
        from collections import OrderedDict, deque

        from pipeedge_tpu.parallel.batcher import ContinuousBatcher
        self.pipe = pipe
        self.spec = spec
        # -- paged KV plane (docs/SERVING.md, pipeedge_tpu/kv) ----------
        # kv_pages > 0 swaps the executor's dense per-request cache
        # slots for page tables over one shared pool (+ the prefix
        # trie); admission then runs on a KV TOKEN budget. The optional
        # prefill fleet (--disaggregate) runs prompt passes on its OWN
        # pipeline and ships KV pages in, so decode waves never share
        # stage-time with prefills.
        self.kv_backend = None
        if kv_pages:
            if pipe.cache_leaves is not None:
                raise NotImplementedError(
                    f"--kv-pages: the {pipe.family.name} family names its "
                    "own cache leaves, and a page of the pool holds the "
                    "plain k, v pair")
            from pipeedge_tpu.kv import PagedKvBackend
            self.kv_backend = PagedKvBackend(pipe, kv_pages,
                                             kv_page_size)
            if spec is not None:
                # page the speculative draft/verify caches onto the
                # plane: target rounds reserve pages from the SAME pool
                # decode requests use (one capacity accountant), the
                # draft model gets its own small pool over its own
                # pipeline geometry (pipeedge_tpu/parallel/speculative)
                from pipeedge_tpu.kv.pool import KvPagePool
                spec.attach_paged(self.kv_backend,
                                  KvPagePool(spec.draft, kv_pages,
                                             kv_page_size))
        self.prefill_fleet = prefill_fleet
        self.prefill_supervisor = prefill_supervisor
        self._prefill_unavailable = None
        self.m_prefill_colocated = None
        if prefill_fleet is not None and self.kv_backend is None:
            raise ValueError("--disaggregate needs --kv-pages (shipped "
                             "KV lands in the paged pool)")
        if prefill_fleet is not None:
            from pipeedge_tpu.kv.fleet import PrefillUnavailable
            self._prefill_unavailable = PrefillUnavailable
            # colocated-fallback accounting (PL501: the reason matrix is
            # known here). "unavailable" = every prefill rank/retry
            # exhausted (docs/FAULT_TOLERANCE.md disaggregated serving);
            # "brownout" = the colocate_prefill rung turned shipping off
            self.m_prefill_colocated = prom.REGISTRY.counter(
                "pipeedge_kv_prefill_colocated_total",
                "prompt passes run colocated in the decode executor "
                "while disaggregation was configured, by reason")
            for reason in ("unavailable", "brownout"):
                self.m_prefill_colocated.declare(reason=reason)
        # -- /metrics + healthz counters (one source of truth) ----------
        # the registry instruments below ARE the state: healthz's stats
        # read them back (stats()), so both surfaces always agree — even
        # across a _Service rebuild in the same process (get_or_create
        # returns the surviving instruments)
        self._edge_itemsize = int(edge_itemsize)
        self.m_requests = prom.REGISTRY.counter(
            "pipeedge_serve_requests_total",
            "generate requests by endpoint and outcome status")
        # full endpoint x outcome matrix from the first scrape (PL501)
        for endpoint in ("/generate", "/generate-speculative"):
            for status in ("200", "503", "504", "error"):
                self.m_requests.declare(endpoint=endpoint, status=status)
        self.m_tokens = prom.REGISTRY.counter(
            "pipeedge_serve_tokens_total", "tokens generated (rows x steps)")
        self.m_latency = prom.REGISTRY.histogram(
            "pipeedge_serve_request_latency_seconds",
            "end-to-end generate latency (request receipt -> result)")
        # request-class x outcome matrix (the request-tracing plane's
        # per-class view; full matrix renders from the first scrape)
        self.m_class_outcome = prom.REGISTRY.counter(
            "pipeedge_requests_by_class_total",
            "generate requests by request class and outcome "
            "(ok / shed / deadline / degraded / error)")
        for cls in REQUEST_CLASSES:
            for outcome in REQUEST_OUTCOMES:
                self.m_class_outcome.declare(**{"class": cls,
                                                "outcome": outcome})
        # flight recorder (docs/OBSERVABILITY.md): always-on event ring +
        # postmortem bundles on 504 / shed / failover / SLO breach
        self.flight = flight.configure(rank=0, out_dir=postmortem_dir)
        # local SLO burn-rate engine (ticked by the governor loop): the
        # per-class outcome counter above feeds the pre-declared
        # pipeedge_slo_burn_rate{class,window} matrix; a fast-window
        # breach writes ONE slo_burn postmortem per overload episode
        self.burn = fleet_obs.BurnRateEngine(
            objective=slo_objective, fast_window_s=slo_burn_fast,
            slow_window_s=slo_burn_slow, threshold=slo_burn_threshold,
            on_breach=self._on_slo_burn)
        self.m_degraded = prom.REGISTRY.counter(
            "pipeedge_serve_degraded_entered_total",
            "failover windows opened via POST /degraded")
        self.m_replays = prom.REGISTRY.counter(
            "pipeedge_serve_failover_replays_total",
            "in-flight requests replayed after a degraded window closed")
        self.m_rejoined = prom.REGISTRY.counter(
            "pipeedge_serve_rejoined_ranks_total",
            "degraded windows closed as HEALED (capacity restored by a "
            "rank rejoining), by rank")
        self.m_last_dead = prom.REGISTRY.gauge(
            "pipeedge_serve_last_dead_rank",
            "rank named by the most recent degraded window (-1 = none)")
        self.m_last_dead.set(-1)
        # distinct name from runtime.py's pipeedge_edge_wire_bytes_total
        # (measured DCN socket bytes, direction/peer labels): these are
        # estimated device-edge activation bytes — merging the two under
        # one family would let sum() silently add different quantities
        self.m_edge_bytes = prom.REGISTRY.counter(
            "pipeedge_serve_edge_wire_bytes_total",
            "per-edge activation bytes moved by completed requests "
            "(prefill + decode steps, estimated from shapes)")
        # the full per-edge matrix renders from the first scrape, not the
        # first request
        for i in range(len(pipe.stages) - 1):
            self.m_edge_bytes.declare(edge=f"{i}->{i + 1}")
        # speculative generations hold THIS lock, not self.cond: plain
        # requests and result waits proceed concurrently (the pipeline's
        # jitted programs are thread-safe; serializing speculative
        # requests with each other bounds their cache memory)
        self.spec_lock = make_lock("serve.speculative")
        self.prefixes = OrderedDict()   # LRU-bounded: handles hold full
        self.spec_prefixes = OrderedDict()   # max_len KV buffers
        self.max_prefixes = max_prefixes
        self._next_rid = 0
        self._next_pid = 0
        # failover window (enter_degraded/exit_degraded): while set, new
        # work is refused with 503 + Retry-After and healthz reports the
        # dead rank; unlike a dead executor it is expected to clear
        self.degraded_info: Optional[dict] = None
        # graceful drain (POST /drain, routed fleets): new admits are
        # refused 503 + Retry-After while in-flight requests complete;
        # the router migrates warm KV and detaches when active hits 0
        self.draining = False
        # replay gate: set on every window close so in-flight requests
        # waiting out a failover wake IMMEDIATELY on recovery instead of
        # polling (the _await_recovery contract)
        self._recovered = threading.Event()
        # observed heal durations (window open -> healed close): the
        # basis of the DERIVED Retry-After when the orchestrator's
        # /degraded post doesn't carry one
        self._heal_s = deque(maxlen=8)
        # -- iteration-level scheduling knobs (docs/SERVING.md) ---------
        # chunked_prefill > 0 splits long prompt passes into fixed-token
        # chunks interleaved with decode steps; step_join wakes the
        # admission queue at every decode-step boundary so joiners ride
        # the next tick instead of the next completion. `_on_step` is a
        # bound closure because the admission controller is constructed
        # AFTER the executor (it needs its concurrency bound).
        self.chunked_prefill = int(chunked_prefill)
        self.step_join = bool(step_join)
        # the one writer of every streamed answer: the executor hands it a
        # tick's tokens in one call (pipeedge_tpu/serving/streams.py)
        self.streams = StreamWriter().start()
        self.executor = ContinuousBatcher(
            pipe, max_active=max_active, kv=self.kv_backend,
            chunk_tokens=self.chunked_prefill,
            prefill_budget=prefill_budget, step_join=self.step_join,
            on_step=self._on_step, on_tokens=self.streams.hand_over)
        # the step programs of the rows that step together, every rung,
        # before the first request: one request alone meets one rung
        self.executor.warm()
        self.executor.start()
        # ONE lock: the prefix registry, the degraded window and the rid
        # counter share the condition the executor's worker ticks under
        self.cond = self.executor.cond
        # -- overload-protection plane (docs/SERVING.md) ----------------
        # admission concurrency mirrors the executor's own bound, so the
        # EDF queue is the ONLY place requests wait and the executor
        # admits a granted request immediately
        concurrency = self.executor.max_active
        self.m_deadline = prom.REGISTRY.counter(
            "pipeedge_deadline_exceeded_total",
            "requests whose deadline expired mid-flight (cancelled at a "
            "decode-step boundary and answered 504)")
        self.admission: Optional[AdmissionController] = None
        if admission_enabled:
            # paged mode: `max_active` becomes a TOKEN budget — each
            # admit charges the request's prompt+max-new-tokens page
            # reservation, so many small requests share the capacity a
            # few dense slots used to pin (docs/SERVING.md)
            self.admission = AdmissionController(
                concurrency=concurrency, queue_capacity=queue_capacity,
                policies=default_policies(class_rates, class_deadlines_s),
                token_budget=(None if self.kv_backend is None
                              else self.kv_backend.pool.tokens_capacity))
        self.brownout: Optional[BrownoutLadder] = None
        self._governor = None
        self._gov_stop = threading.Event()
        self.governor_interval = float(governor_interval)
        if brownout_enabled:
            self.brownout = BrownoutLadder(
                brownout_marks if brownout_marks is not None
                else Watermarks(), clamp_new_tokens=clamp_new_tokens,
                clamp_chunk_tokens=clamp_chunk_tokens)
            if self.kv_backend is not None:
                # the evict_cold_pages rung's lever: reclaim cached-but-
                # idle prefix pages before any request class is shed
                self.brownout.evict_hook = self.kv_backend.evict_cold_all
        # the governor also owns the paged-KV orphan sweep (leak audit,
        # docs/FAULT_TOLERANCE.md) and the SLO burn-rate tick; the burn
        # engine always exists, so the thread always runs
        self._governor = threading.Thread(target=self._governor_loop,
                                          daemon=True,
                                          name="brownout-governor")
        self._governor.start()

    def _on_step(self):
        """Executor decode-step hook (--step-join): re-drive the EDF
        admission queue at every step boundary, so a joiner whose slot
        or token charge just freed is granted mid-request instead of
        waiting out the whole completion. Cheap no-op when the queue is
        empty; tolerant of construction order (the executor exists
        before the admission controller does)."""
        adm = getattr(self, "admission", None)
        if adm is not None:
            adm.notify_step()

    @property
    def dead(self) -> Optional[BaseException]:
        return self.executor.dead

    def add_prefix(self, ids):
        with self.cond:
            self._check_admittable()
            if self.kv_backend is not None:
                # paged mode: registration is just the TOKEN LIST — the
                # prefix trie dedups the actual prefill across every
                # request that uses it (first use pays one prompt pass;
                # later uses share its pages), so no max_len KV buffers
                # are pinned per registration
                tokens = [int(t) for t in ids]
                if not tokens:
                    raise ValueError("prefix must be non-empty")
                pid = f"p{self._next_pid}"
                self._next_pid += 1
                self.prefixes[pid] = {"tokens": tokens,
                                      "len": len(tokens)}
                while len(self.prefixes) > self.max_prefixes:
                    self.prefixes.popitem(last=False)
                return pid, len(tokens)
            # precompute BOTH handles before registering either, so a
            # draft-side failure cannot leave a half-registered prefix
            # (usable plainly, 400ing speculatively). The target handle
            # is shared — the draft model's K/V is the only extra state.
            target = self.pipe.precompute_prefix(ids)
            draft = (self.spec.draft.precompute_prefix(ids)
                     if self.spec is not None else None)
            pid = f"p{self._next_pid}"
            self._next_pid += 1
            self.prefixes[pid] = target
            if draft is not None:
                self.spec_prefixes[pid] = {"target": target,
                                           "draft": draft}
            while len(self.prefixes) > self.max_prefixes:
                old, _ = self.prefixes.popitem(last=False)  # evict oldest
                self.spec_prefixes.pop(old, None)
            return pid, target["len"]

    def _check_dead(self):
        dead = self.dead
        if dead is not None:
            raise RuntimeError(f"serving worker died: {dead!r}")

    # -- replica-to-replica KV migration (docs/FAULT_TOLERANCE.md) ------

    def kv_export(self, ids):
        """POST /kv/export: this replica's warm KV pages for a prompt
        prefix, as a base64 wire-v2 ship blob (kv/ship.py) — the router
        ships them to a survivor during a graceful drain. Returns
        (blob_b64 | None, tokens_covered, pages)."""
        if self.kv_backend is None:
            raise ValueError("KV export needs --kv-pages (dense cache "
                             "slots have no page plane to export)")
        from pipeedge_tpu.kv import ship
        from pipeedge_tpu.serving.router import encode_ship_blob
        out = self.kv_backend.export_prefix([int(t) for t in ids])
        if out is None:
            return None, 0, 0
        frames, plen, pages = out
        return encode_ship_blob(frames), plen, pages

    def kv_import(self, ids, blob_b64):
        """POST /kv/import: install a shipped prefix into this
        replica's page pool + trie (idempotent — an already-cached
        prefix installs 0 pages). Returns pages installed."""
        if self.kv_backend is None:
            raise ValueError("KV import needs --kv-pages")
        from pipeedge_tpu.kv import ship
        from pipeedge_tpu.serving.router import decode_ship_blob
        tensors = decode_ship_blob(blob_b64)
        handle = ship.decode_kv_ship(tensors, self.pipe.dtype)
        return self.kv_backend.install_prefix([int(t) for t in ids],
                                              handle)

    # -- brownout governor ----------------------------------------------

    def _live_request_ids(self):
        """Snapshot of every live executor request id — the orphan
        sweep's liveness set. None = the snapshot raced a mutation
        (skip this sweep; the next tick retries)."""
        live = self.executor.live_rids()
        if live is not None and self.spec is not None:
            # paged speculative rounds reserve pages from the decode
            # plane's pool under their own owner ids — union them in so
            # a mid-generate speculative request survives the sweep
            live |= self.spec.live_rids()
        return live

    def _on_slo_burn(self, cls, burn):
        """BurnRateEngine breach hook (edge-triggered, governor thread):
        capture the serving state that burned the budget."""
        self.flight.note("slo_burn_breach", request_class=cls,
                         burn=round(burn, 3))
        ctx = self.bundle_context()
        ctx["slo_burn"] = {"class": cls, "burn_rate": round(burn, 4),
                           "objective": self.burn.objective,
                           "threshold": self.burn.threshold}
        self.flight.maybe_dump("slo_burn", context=ctx)

    def _governor_loop(self):
        """Periodic brownout tick: windowed p95 of the request-latency
        histogram (delta between scrapes of the SAME instrument /metrics
        renders) + admission queue depth drive the ladder; the degraded
        lifecycle floors it (healing implies at least level 1). The
        ladder's shed classes feed straight into admission. With a paged
        KV backend the loop doubles as the leak audit: every ~2s the
        pool's owner ledger is reconciled against executor liveness, so
        a submitter/shipper that died mid-request strands zero pages
        (pipeedge_kv_pages_leaked_total counts the reclaims)."""
        prev_counts, prev_n = self.m_latency.snapshot()
        last_level = self.brownout.level if self.brownout is not None else 0
        sweep_every = max(1, round(2.0 / self.governor_interval))
        ticks = 0
        while not self._gov_stop.wait(self.governor_interval):
            ticks += 1
            counts, n = self.m_latency.snapshot()
            delta = [c - p for c, p in zip(counts, prev_counts)]
            p95 = prom.percentile_from_counts(
                self.m_latency.buckets, delta, n - prev_n, 95.0)
            prev_counts, prev_n = counts, n
            depth = (self.admission.queue_depth
                     if self.admission is not None else 0)
            self.burn.update(fleet_obs.BurnRateEngine.counts_from_counter(
                self.m_class_outcome))
            if self.brownout is not None:
                self.brownout.set_floor(
                    1 if self.degraded_info is not None else 0)
                level = self.brownout.update(depth, p95)
                if self.admission is not None:
                    self.admission.set_shed_classes(
                        self.brownout.shed_classes())
                if level != last_level:
                    t = time.monotonic_ns()
                    telemetry.record("serve", f"brownout:{level}", t, t)
                    self.flight.note("brownout", level=level,
                                     queue_depth=depth, p95_s=p95)
                    if level >= 2 and level > last_level:
                        # stepping INTO the clamp/shed rungs is the
                        # SLO-breach trigger: capture the state that
                        # drove the ladder up
                        self.flight.maybe_dump(
                            "slo", context=self.bundle_context())
                    last_level = level
                if self.chunked_prefill:
                    # the clamp_tokens rung's second lever: shrink the
                    # prefill chunk size while hot so decode steps get
                    # more step boundaries per second (identity when the
                    # lever is unarmed — clamp_chunk_tokens == 0)
                    want = self.brownout.clamp_chunk(self.chunked_prefill)
                    if self.executor.chunk_tokens != want:
                        self.executor.set_chunk_tokens(want)
                        self.flight.note("chunk_clamp", chunk_tokens=want)
            if self.kv_backend is not None and ticks % sweep_every == 0:
                # liveness passed as a CALLABLE: the sweep snapshots
                # the owner ledger FIRST, liveness second — a request
                # admitted between the two reads is provably live, so
                # its in-use pages can never be taken for orphans
                leaked = self.kv_backend.sweep_orphans(
                    self._live_request_ids)
                if leaked:
                    self.flight.note("kv_pages_reclaimed", pages=leaked)
                if self.spec is not None:
                    d_leaked = self.spec.sweep_orphans()
                    if d_leaked:
                        self.flight.note("draft_pages_reclaimed",
                                         pages=d_leaked)

    # -- failover window ------------------------------------------------

    def _derived_retry_after(self) -> float:
        """Retry-After for a window the orchestrator opened WITHOUT a
        hint: the median observed heal time (how long capacity actually
        took to come back in this process's history), 5 s until a heal
        has been seen."""
        if self._heal_s:
            med = sorted(self._heal_s)[len(self._heal_s) // 2]
            return min(60.0, max(0.5, med))
        return 5.0

    def enter_degraded(self, dead_rank=None,
                       retry_after: Optional[float] = None):
        """Open a failover window: admission refuses new work with
        503 + Retry-After until `exit_degraded` (the orchestrator's signal
        that the backing pipeline recovered). `retry_after=None` derives
        the hint from observed heal telemetry (`_derived_retry_after`)."""
        if retry_after is None:
            retry_after = self._derived_retry_after()
        self._recovered.clear()
        with self.cond:
            self.degraded_info = {"dead_rank": dead_rank,
                                  "since": time.monotonic(),
                                  "retry_after": float(retry_after),
                                  "phase": "degraded"}
        self.m_degraded.inc()
        if dead_rank is not None:
            self.m_last_dead.set(int(dead_rank))
        # failover IS a flight-recorder trigger: the bundle carries the
        # brownout/admission state at the moment the window opened
        self.flight.note("degraded", dead_rank=dead_rank,
                         retry_after=retry_after)
        self.flight.maybe_dump("failover", context=self.bundle_context())

    def mark_healing(self):
        """The dead rank rejoined and the orchestrator is restoring the
        partition: the window stays open (new work still bounces with
        Retry-After — the heal lands at a round boundary, not instantly),
        but /healthz distinguishes `healing` from plain `degraded`. A
        no-op when no window is open (a stray healing signal must not
        resurrect a closed window)."""
        with self.cond:
            if self.degraded_info is not None:
                self.degraded_info["phase"] = "healing"

    def exit_degraded(self, healed: bool = False, rank=None):
        """Close the window. `healed=True` records the close as a
        capacity restoration (the orchestrator's {"degraded": false,
        "healed": true} form) on pipeedge_serve_rejoined_ranks_total —
        distinct from a plain manual clear — and feeds the window's
        duration into the heal-telemetry history future windows derive
        their Retry-After from."""
        with self.cond:
            was_open = self.degraded_info is not None
            if healed and was_open:
                self._heal_s.append(
                    time.monotonic() - self.degraded_info["since"])
            self.degraded_info = None
        self._recovered.set()     # wake replay waiters immediately
        self.flight.note("degraded_closed", healed=healed, rank=rank)
        if healed and was_open:
            # unlabeled on purpose: healthz stats() reads the same series
            # back (value() is per-label-set); the healed rank stays
            # visible as last_dead_rank history
            self.m_rejoined.inc()

    def _check_admittable(self):
        deg = self.degraded_info
        if deg is not None:
            raise ServiceDegraded(deg["dead_rank"], deg["retry_after"])
        if self.draining:
            # drains don't heal: the Retry-After tells the client to go
            # find another replica (the router already stopped routing
            # here; this is the race window's backstop)
            raise RuntimeError("draining: this replica admits no new "
                               "requests")

    def begin_drain(self):
        """POST /drain: stop admitting, let in-flight work finish. The
        ROUTER owns the rest of the lifecycle (migrate warm prefixes,
        detach, respawn) — this side only has to refuse new admits and
        report `active` honestly in /healthz."""
        self.draining = True
        self.flight.note("drain_begin")

    def _await_recovery(self) -> bool:
        """Block until the degraded window closes (True) or its retry
        budget runs out / the worker is truly dead (False). The replay
        gate for a request that was in flight when the failover began.

        Waits on the `_recovered` event `exit_degraded` signals, so a
        heal admits the replay IMMEDIATELY — the 2x retry_after budget is
        only the give-up bound, not a polling interval. The short wait
        slices exist solely to notice a TRUE executor death mid-window
        (nothing signals an event for that) without holding the handler
        thread for the whole budget."""
        with self.cond:
            deg = self.degraded_info
            if deg is None:
                return False   # the failure was not a failover window
        deadline = time.monotonic() + 2 * deg["retry_after"]
        while True:
            left = deadline - time.monotonic()
            if left <= 0 or self.dead is not None:
                return False
            if self._recovered.wait(timeout=min(0.5, left)):
                return (self.dead is None
                        and self.degraded_info is None)

    # -- admission plumbing (docs/SERVING.md) ---------------------------

    def speculative_allowed(self) -> bool:
        """Brownout rung 1 (`no_speculative`) is the ladder's first,
        cheapest degradation: speculative requests fall back to plain
        greedy (token-identical) instead of occupying the serialized
        draft/verify path."""
        return self.brownout is None or self.brownout.allow_speculative()

    def mint_rid(self) -> str:
        """Mint one request id — THE request identity every span, flight
        event, response body, and postmortem bundle correlates on
        (docs/OBSERVABILITY.md request tracing). The trace CONTEXT is
        built where the class/deadline are known (generate paths)."""
        with self.cond:
            n = self._next_rid
            self._next_rid += 1
        return f"q{n}"

    def kv_tokens(self, ids, new_tokens) -> int:
        """The admission token charge of one request under the paged KV
        plane: its prompt + max-new-tokens page reservation (0 when
        dense caches / no admission — slot-only admission)."""
        if self.kv_backend is None or self.admission is None or not ids:
            return 0
        return self.kv_backend.tokens_needed(
            max(len(r) for r in ids), int(new_tokens), len(ids))

    def admit(self, request_class: str, deadline_s=None, rid=None,
              tokens: int = 0):
        """Acquire an admission ticket (blocking, EDF order) + its
        absolute deadline. Returns (ticket, deadline); raises
        `AdmissionShed` (503 + dynamic Retry-After) on shed, KeyError on
        an unknown class (the handler's 400). The caller must hand the
        ticket to `generate(..., ticket=...)`, which releases it. `rid`
        request-tags the queue-wait span, the ticket, and the flight
        events, so a trace/bundle names WHO waited and who was shed.
        `tokens` is the KV-token charge under a token budget
        (`kv_tokens`)."""
        if self.admission is None:
            deadline = (None if deadline_s is None
                        else time.monotonic() + float(deadline_s))
            return None, deadline
        deadline = self.admission.deadline_for(request_class, deadline_s)
        # spans recorded by hand, not a context manager: an `admit:`
        # sample must mean "queue wait of an ADMITTED request" (the
        # report's admit_wait_ms) — a shed waiter's wasted wait records
        # under its `shed:` span instead of skewing that stat
        t0 = time.monotonic_ns()
        try:
            ticket = self.admission.admit(request_class, deadline,
                                          rid=rid, tokens=tokens)
        except AdmissionShed as exc:
            telemetry.record(
                "serve", f"shed:{exc.request_class}:{exc.reason}",
                t0, time.monotonic_ns(), rid=rid)
            self.flight.note("shed", rid=rid, cls=exc.request_class,
                             reason=exc.reason,
                             retry_after=exc.retry_after)
            # gate BEFORE assembling the context: a shed storm must not
            # pay a full serving snapshot per cooldown-suppressed dump
            if self.flight.would_dump("shed"):
                self.flight.maybe_dump("shed", rid=rid,
                                       context=self.bundle_context())
            raise
        telemetry.record("serve", f"admit:{request_class}",
                         t0, time.monotonic_ns(), rid=rid)
        self.flight.note("admit", rid=rid, cls=request_class,
                         wait_ms=round((time.monotonic_ns() - t0) / 1e6, 3))
        return ticket, deadline

    def bundle_context(self) -> dict:
        """The serving-state slice every postmortem bundle carries:
        admission + brownout snapshots, the degraded window, and the
        executor stats — what was true of the service when the trigger
        fired."""
        ctx = {"serving": self.serving_stats(), "stats": self.stats()}
        deg = self.degraded_info
        if deg is not None:
            ctx["degraded"] = {"dead_rank": deg["dead_rank"],
                               "phase": deg.get("phase"),
                               "since_s": round(time.monotonic()
                                                - deg["since"], 3)}
        ctx["latency_exemplars"] = self.m_latency.exemplars()
        return ctx

    def dump_postmortem(self, rid=None, trigger="manual"):
        """POST /debug/dump's implementation: write a bundle NOW (manual
        dumps bypass the cooldown). Returns the bundle path."""
        return self.flight.maybe_dump(trigger, rid=rid,
                                      context=self.bundle_context())

    def flight_stats(self) -> dict:
        """The /healthz `flight` block — shared with /metrics through the
        same counter family (pipeedge_postmortems_written_total)."""
        return {"postmortems_written_total": self.flight.written_total(),
                "last_postmortem": self.flight.last_path(),
                "events_dropped": self.flight.dropped}

    def retry_after_hint(self) -> float:
        """Best current 'come back in N seconds' estimate — the value
        every 503 path attaches: the open degraded window's hint, else
        the admission plane's queue-drain estimate."""
        deg = self.degraded_info
        if deg is not None:
            return deg["retry_after"]
        if self.admission is not None:
            return self.admission.retry_after()
        return 5.0

    def serving_stats(self) -> dict:
        """The /healthz `serving` block (admission + brownout state)."""
        s = {"deadline_exceeded_total": int(self.m_deadline.value())}
        if self.admission is not None:
            s["admission"] = self.admission.snapshot()
        if self.brownout is not None:
            s["brownout"] = self.brownout.snapshot()
        if self.chunked_prefill or self.step_join:
            # iteration-level scheduling state: the configured chunk
            # size, the EFFECTIVE one (brownout may have clamped it),
            # and how many chunk waves have run: that chunked prefill
            # engaged (docs/SERVING.md)
            s["scheduler"] = {
                "chunked_prefill": self.chunked_prefill,
                "chunk_tokens": self.executor.chunk_tokens,
                "step_join": self.step_join,
                "prefill_chunks": int(
                    self.executor.snapshot()["prefill_chunks"]),
            }
        if self.kv_backend is not None:
            s["kv"] = self.kv_backend.snapshot()
            s["kv"]["disaggregated"] = self.prefill_fleet is not None
            # the leak audit's health surface: running total of page
            # references the orphan sweep reclaimed (0 = no leaks)
            s["kv"]["leaked"] = s["kv"]["pool"]["leaked"]
            fleet_snapshot = getattr(self.prefill_fleet, "snapshot", None)
            if fleet_snapshot is not None:
                s["kv"]["prefill"] = fleet_snapshot()
                if self.m_prefill_colocated is not None:
                    s["kv"]["prefill"]["colocated"] = {
                        r: int(self.m_prefill_colocated.value(reason=r))
                        for r in ("unavailable", "brownout")}
            if self.prefill_supervisor is not None:
                s["kv"].setdefault("prefill", {})["workers"] = \
                    self.prefill_supervisor.snapshot()
        return s

    def generate_speculative(self, ids, new_tokens, prefix_id=None,
                             request_class="interactive",
                             deadline_s=None, ticket=None, rid=None):
        """Greedy speculative decoding (token-identical to plain greedy;
        the draft only changes the dispatch count). Holds only the
        dedicated spec lock during the generation — concurrent plain
        requests keep flowing through the executor. Admission applies
        like any generate (the deadline guards the QUEUE wait; the
        speculative loop itself has no mid-flight cancel boundary —
        docs/SERVING.md)."""
        t0 = time.monotonic()
        if rid is None:
            rid = self.mint_rid()
        tctx = telemetry.TraceContext(rid, request_class,
                                      deadline_ms=None if deadline_s is None
                                      else deadline_s * 1e3,
                                      parent="serve.speculative")
        released = self.admission is None
        try:
            strip = 0
            if self.kv_backend is not None and prefix_id is not None:
                # paged mode: the prefix becomes prepended tokens BEFORE
                # the token charge is computed (the page reservation
                # must cover the full prompt; the trie makes the shared
                # part nearly free to re-run)
                with self.cond:
                    self._check_dead()
                    self._check_admittable()
                    pkw = {"prefix_id": prefix_id}
                    ids, strip = self._expand_prefix(ids, pkw)
                prefix_id = None
            if ticket is None and self.admission is not None:
                # paged speculative rounds reserve up to gamma extra
                # verify positions past new_tokens — charge for them
                gamma = self.spec.gamma if self.spec is not None else 0
                ticket, _ = self.admit(
                    request_class, deadline_s, rid=rid,
                    tokens=self.kv_tokens(ids, int(new_tokens) + gamma))
            completed = False
            try:
                with telemetry.trace_scope(tctx):
                    out = self._generate_speculative_once(ids, new_tokens,
                                                          prefix_id,
                                                          rid=rid)
                    if strip:
                        out = out[:, strip:]
                completed = True
            finally:
                if not released:
                    # failures must not feed the service-rate estimator
                    # (they would inflate the rate Retry-After divides by)
                    self.admission.release(ticket, completed=completed)
                    released = True
        except AdmissionShed:
            self.m_requests.inc(endpoint="/generate-speculative",
                                status="503")
            self.m_class_outcome.inc(**{"class": request_class,
                                        "outcome": "shed"})
            raise
        except ServiceDegraded:
            self.m_requests.inc(endpoint="/generate-speculative",
                                status="503")
            self.m_class_outcome.inc(**{"class": request_class,
                                        "outcome": "degraded"})
            raise
        except BaseException:
            self.m_requests.inc(endpoint="/generate-speculative",
                                status="error")
            self.m_class_outcome.inc(**{"class": request_class,
                                        "outcome": "error"})
            raise
        self.m_latency.observe(time.monotonic() - t0, exemplar=rid)
        self.m_requests.inc(endpoint="/generate-speculative", status="200")
        self.m_class_outcome.inc(**{"class": request_class,
                                    "outcome": "ok"})
        self.m_tokens.inc(len(ids) * int(new_tokens))
        self._account_edge_bytes(ids, int(new_tokens))
        return out

    def _generate_speculative_once(self, ids, new_tokens, prefix_id,
                                   rid=None):
        import numpy as np
        if self.spec is None:
            raise KeyError("server started without --draft-model; "
                           "speculative generation unavailable")
        with self.cond:                     # resolve prefix briefly
            self._check_dead()
            self._check_admittable()
            prefix = None
            if prefix_id is not None:
                if prefix_id not in self.spec_prefixes:
                    raise KeyError(
                        f"unknown prefix_id {prefix_id!r} for speculative "
                        "generation (register via /prefix while the "
                        "draft model is configured)")
                self.prefixes.move_to_end(prefix_id)   # LRU touch
                prefix = self.spec_prefixes[prefix_id]
        with self.spec_lock, telemetry.span("serve", "speculative"):
            # rid threads through to the paged allocator as the page
            # owner id, so the governor's orphan sweep can name it
            return np.asarray(self.spec.generate(ids, new_tokens,
                                                 prefix=prefix, rid=rid))

    def prevalidate(self, ids, new_tokens, kw):
        """Resolve prefix_id and run the full admission validation WITHOUT
        submitting — the streaming path needs errors raised BEFORE the
        200/chunked headers commit (a status-checking client must see
        400, not a 200 whose body is an error line). Returns `(ids, kw)`
        with the prefix resolved: the dense handle in `kw["prefix"]`, or
        — paged mode — the prefix TOKENS prepended to `ids` (plus
        `kw["strip_prefix"]` so the response still omits them)."""
        from pipeedge_tpu.parallel.batcher import _build_request
        kw = dict(kw)
        with self.cond:
            self._check_dead()
            self._check_admittable()
            if self.kv_backend is not None:
                ids, strip = self._expand_prefix(ids, kw)
                if strip:
                    kw["strip_prefix"] = strip
            else:
                self._resolve_prefix(kw)
        _build_request(self.pipe, "__prevalidate__", ids, new_tokens,
                       kw.get("temperature", 0.0), kw.get("top_k", 0),
                       kw.get("seed", 0), kw.get("eos_token"),
                       kw.get("pad_token"), kw.get("prefix"))
        return ids, kw

    def _resolve_prefix(self, kw):
        pid = kw.pop("prefix_id", None)
        if pid is not None:
            if pid not in self.prefixes:
                raise KeyError(f"unknown prefix_id {pid!r} (evicted "
                               "or never registered)")
            self.prefixes.move_to_end(pid)     # LRU touch
            kw["prefix"] = self.prefixes[pid]

    def _expand_prefix(self, ids, kw):
        """Paged mode: a `prefix_id` becomes its registered tokens
        prepended to every prompt row — the prefix trie turns the
        repeated prefill into page reuse (one prompt pass fleet-wide,
        then shared pages). Returns (expanded ids, strip); callers
        slice `strip` columns off the result so the response matches
        the dense handle contract (suffix + continuation)."""
        pid = kw.pop("prefix_id", None)
        if pid is None:
            return ids, 0
        if pid not in self.prefixes:
            raise KeyError(f"unknown prefix_id {pid!r} (evicted "
                           "or never registered)")
        self.prefixes.move_to_end(pid)         # LRU touch
        tokens = self.prefixes[pid]["tokens"]
        return [list(tokens) + [int(t) for t in r] for r in ids], \
            len(tokens)

    def generate(self, ids, new_tokens, on_token=None,
                 request_class="interactive", deadline_s=None,
                 ticket=None, deadline=None, rid=None, **kw):
        """One admitted generation. `request_class`/`deadline_s` drive
        the admission plane; a pre-admitted `ticket` (+ its absolute
        `deadline`) comes from the streaming path, which must shed
        BEFORE the chunked headers commit. The deadline rides into the
        executor, whose decode-step expiry check fires the request's
        `cancel` flag — a mid-flight expiry surfaces as
        `DeadlineExceeded` (HTTP 504). `rid` is the minted request id
        (mint_rid); every span, flight event, and the executor's
        per-stage spans carry it."""
        t0 = time.monotonic()
        if rid is None:
            rid = self.mint_rid()
        tctx = telemetry.TraceContext(rid, request_class,
                                      deadline_ms=None if deadline_s is None
                                      else deadline_s * 1e3,
                                      parent="serve.generate")
        # paged mode: a prefix_id becomes prepended tokens BEFORE the
        # token charge is computed (the reservation must cover the full
        # prompt; the trie makes the shared part nearly free to run)
        strip = int(kw.pop("strip_prefix", 0))
        if self.kv_backend is not None and kw.get("prefix_id") is not None:
            with self.cond:
                ids, strip = self._expand_prefix(ids, kw)
        completed = False
        try:
            if ticket is None and deadline is None:
                # the streaming path pre-admits (its ticket, or with
                # --no-admission just the computed deadline) — don't
                # clobber a deadline that arrives without a ticket
                ticket, deadline = self.admit(
                    request_class, deadline_s, rid=rid,
                    tokens=self.kv_tokens(ids, new_tokens))
            try:
                if self.brownout is not None:
                    new_tokens = self.brownout.clamp(new_tokens)
                cancel = kw.get("cancel")
                if deadline is not None:
                    if cancel is None:
                        cancel = threading.Event()
                        kw["cancel"] = cancel
                    kw["deadline"] = deadline
                with telemetry.trace_scope(tctx), \
                        telemetry.span("serve", "generate", rid=rid):
                    out = self._generate_policied(ids, new_tokens,
                                                  on_token, kw, rid=rid)
                now = time.monotonic()
                if (deadline is not None and now >= deadline
                        and cancel.is_set()):
                    # the executor cancelled it at a decode-step
                    # boundary: the work was cut short, answer 504
                    completed = True   # it DID occupy a full slot
                    raise DeadlineExceeded(
                        request_class, deadline_s
                        if deadline_s is not None else deadline - t0)
                completed = True
            finally:
                # generate releases ANY ticket it holds: the streaming
                # handler hands its pre-admitted ticket over with the
                # request and never touches it again
                if ticket is not None and self.admission is not None:
                    self.admission.release(ticket, completed=completed)
        except AdmissionShed:
            self.m_requests.inc(endpoint="/generate", status="503")
            self.m_class_outcome.inc(**{"class": request_class,
                                        "outcome": "shed"})
            raise
        except DeadlineExceeded:
            self.m_deadline.inc()
            self.m_requests.inc(endpoint="/generate", status="504")
            self.m_class_outcome.inc(**{"class": request_class,
                                        "outcome": "deadline"})
            # a 504 is exactly the artifact the flight recorder exists
            # for: which stage/queue/brownout rung ate the budget
            self.flight.note("deadline", rid=rid, cls=request_class,
                             budget_s=deadline_s,
                             elapsed_ms=round((time.monotonic() - t0) * 1e3,
                                              3))
            self.flight.maybe_dump("deadline", rid=rid,
                                   context=self.bundle_context())
            raise
        except ServiceDegraded:
            self.m_requests.inc(endpoint="/generate", status="503")
            self.m_class_outcome.inc(**{"class": request_class,
                                        "outcome": "degraded"})
            raise
        except BaseException:
            self.m_requests.inc(endpoint="/generate", status="error")
            self.m_class_outcome.inc(**{"class": request_class,
                                        "outcome": "error"})
            raise
        elapsed = time.monotonic() - t0
        # the exemplar links a latency-histogram bucket back to THIS
        # request's trace id: a p99 spike on a dashboard resolves to a
        # trace_report --request invocation (docs/OBSERVABILITY.md)
        self.m_latency.observe(elapsed, exemplar=rid)
        self.m_requests.inc(endpoint="/generate", status="200")
        self.m_class_outcome.inc(**{"class": request_class,
                                    "outcome": "ok"})
        self.m_tokens.inc(len(ids) * int(new_tokens))
        self._account_edge_bytes(ids, int(new_tokens))
        self.flight.note("done", rid=rid, cls=request_class,
                         ms=round(elapsed * 1e3, 3))
        # paged prefix contract: the response omits the prepended prefix
        return out[:, strip:] if strip else out

    def _generate_policied(self, ids, new_tokens, on_token, kw, rid=None):
        with self.cond:
            self._check_dead()
            self._check_admittable()   # degraded: 503 + Retry-After
        try:
            return self._generate_once(ids, new_tokens, on_token, kw,
                                       rid=rid)
        except ServiceDegraded:
            raise
        except RuntimeError:
            # the executor failed while a failover window was open: the
            # request was in flight when the stage died. Replay it once
            # after recovery instead of surfacing the transient — except
            # streamed requests, whose partial output cannot be unsent.
            if on_token is not None or kw.get("stream") is not None \
                    or not self._await_recovery():
                raise
            self.m_replays.inc()
            self.flight.note("replay", rid=rid)
            # derived executor id: the failed attempt may still hold the
            # original rid in the executor's live set, and the replay's
            # spans should be distinguishable from the first try's while
            # staying greppable by prefix
            return self._generate_once(ids, new_tokens, on_token, kw,
                                       rid=None if rid is None
                                       else f"{rid}.replay")

    def _account_edge_bytes(self, ids, new_tokens: int) -> None:
        """Per-edge activation traffic of one completed request: every
        inter-stage boundary moves a [B, S, H] prefill payload plus a
        [B, 1, H] payload per decode step (host-driven device edges — the
        serving analogue of the DCN wire counters)."""
        n_edges = len(self.pipe.stages) - 1
        if n_edges <= 0:
            return
        hidden = getattr(self.pipe.cfg, "hidden_size", 0)
        prompt_len = max(len(r) for r in ids) if ids else 0
        per_edge = (len(ids) * (prompt_len + max(0, new_tokens - 1))
                    * hidden * self._edge_itemsize)
        for i in range(n_edges):
            self.m_edge_bytes.inc(per_edge, edge=f"{i}->{i + 1}")

    def _generate_once(self, ids, new_tokens, on_token, kw, rid=None):
        # the trace rid doubles as the EXECUTOR request id: the mapping
        # between an HTTP request and its executor lifecycle is identity,
        # and the executor's per-stage spans tag it for free (_run_stage)
        if rid is None:
            rid = self.mint_rid()
        if self.prefill_fleet is not None and kw.get("shipped") is None:
            # disaggregated: the prompt pass runs on the PREFILL fleet's
            # own pipeline and ships KV pages in — the decode executor
            # below only ever runs decode steps, so one tenant's long
            # prompt no longer stretches everyone else's inter-token
            # latency (docs/SERVING.md disaggregation). EXCEPT when the
            # prefix trie already covers the prompt's full pages: then
            # the only prompt work left is a short suffix span, cheaper
            # run in place than re-prefilled remotely and re-shipped.
            route_local = False
            if self.brownout is not None \
                    and not self.brownout.allow_disaggregate():
                # brownout rung 4 (colocate_prefill): the plane is hot
                # enough that the ship edge's latency + fault surface
                # costs more than prefill isolation buys — degrade
                # disaggregate -> colocated deliberately
                route_local = True
                self.m_prefill_colocated.inc(reason="brownout")
                self.flight.note("prefill_colocated", rid=rid,
                                 reason="brownout")
            if not route_local and len(ids) == 1:
                toks = [int(t) for t in ids[0]]
                matched = self.kv_backend.shared_prompt_tokens(toks)
                route_local = (matched > 0 and matched >= len(toks)
                               - self.kv_backend.page_size)
            if not route_local:
                try:
                    kw["shipped"] = self.prefill_fleet.prefill(ids,
                                                               rid=rid)
                except self._prefill_unavailable as exc:
                    # every prefill rank/retry exhausted: the request
                    # SURVIVES — the decode executor runs the prompt
                    # pass itself (token-identical; the p99 isolation is
                    # what degrades, not the request)
                    self.m_prefill_colocated.inc(reason="unavailable")
                    self.flight.note("prefill_colocated", rid=rid,
                                     reason="unavailable",
                                     error=str(exc))
        with self.cond:        # re-entrant: one hold, look-up to hand-over
            self._resolve_prefix(kw)
            self.executor.submit(rid, ids, new_tokens, on_token=on_token,
                                 **kw)
        return self.executor.wait(rid)

    def stats(self):
        """Lock-free best-effort snapshot for /healthz (GIL-atomic reads;
        momentary inconsistency is fine for health)."""
        s = self.executor.snapshot()
        s["prefixes"] = len(self.prefixes)
        # degraded/failover history: read back from the SAME registry
        # instruments /metrics renders, so the two surfaces cannot diverge
        s["degraded_entered_total"] = int(self.m_degraded.value())
        s["failover_replays_total"] = int(self.m_replays.value())
        s["rejoined_ranks_total"] = int(self.m_rejoined.value())
        last = self.m_last_dead.value()
        s["last_dead_rank"] = (None if last is None or last < 0
                               else int(last))
        return s

    def stop(self):
        self._gov_stop.set()
        if self.admission is not None:
            self.admission.close()   # shed every queued waiter (shutdown)
        self.executor.stop()
        # after the executor: a handler whose request that stop failed
        # finds no writer and returns without a final line
        self.streams.stop()
        # tear the ship plane down LAST: in-flight prefills were already
        # failed fast by the executor stop above
        close = getattr(self.prefill_fleet, "close", None)
        if close is not None:
            close()
        if self.prefill_supervisor is not None:
            self.prefill_supervisor.stop()


PROFILE_MAX_SECONDS = 60.0


class _HTTPServer(ThreadingHTTPServer):
    """The kernel's accept queue at 128 where socketserver asks for 5:
    under a burst of arrivals a full queue resets connections before
    admission can answer them (503 and Retry-After are the server's to
    say, docs/SERVING.md)."""
    request_queue_size = 128


def _profile(out_dir, seconds):
    """POST /debug/profile: one JAX profiler session of `seconds` into
    `out_dir`, taken with the options of every trace this repo takes
    (utils/tracing.profile_options). While it runs, every telemetry span
    of the process is a host annotation on the trace. JAX allows one
    session a process: a second start raises RuntimeError."""
    import jax
    from pipeedge_tpu.utils import tracing
    tracing.start_trace(out_dir)
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()


def make_handler(service, model_name, profile_dir=None):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"      # chunked transfer needs 1.1

        def log_message(self, *a):      # quiet server
            pass

        def _send(self, code, obj, headers=()):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for name, value in headers:
                self.send_header(name, value)
            self.end_headers()
            self.wfile.write(body)

        def _stream_generate(self, ids, new_tokens, kw,
                             request_class="interactive", deadline_s=None,
                             rid=None):
            """Chunked x-ndjson response: one line per decode step as the
            token lands, then the authoritative final line. This thread
            sends the headers, hands the request to the executor with a
            stream of the service's ONE writer thread, and sleeps until
            the request ends; the executor hands every running stream's
            token to the writer in one call a tick, and the writer formats
            and writes the lines (pipeedge_tpu/serving/streams.py), so
            streaming never stalls the executor and a step of 48 rows
            wakes one thread.

            A client that disconnects mid-stream (write fails), or that
            takes nothing for too long, sets the request's `cancel` flag:
            the executor completes the request at its next pick instead
            of decoding to the cap, so dead requests free their admission
            slot / cache memory early (repeated disconnects could
            otherwise occupy every max_active slot with vanished
            clients)."""
            t0 = time.monotonic()
            # validate BEFORE headers commit: bad requests still 400
            # (raises into do_POST's error mapping) and don't spend
            # admission tokens; then ADMIT before headers commit too — a
            # shed must surface as a real 503 + Retry-After, not a 200
            # whose body is an error line. After this point failures
            # surface as a terminal {"error": ...} stream line.
            ids, kw = service.prevalidate(ids, new_tokens, kw)
            if rid is None:
                rid = service.mint_rid()
            try:
                ticket, deadline = service.admit(
                    request_class, deadline_s, rid=rid,
                    tokens=service.kv_tokens(ids, new_tokens))
            except AdmissionShed:
                # the non-streaming path counts its shed inside
                # generate(); a streaming shed never reaches generate(),
                # so both counters are settled here — the class x outcome
                # matrix must reconcile against the 503s either way
                service.m_requests.inc(endpoint="/generate", status="503")
                service.m_class_outcome.inc(**{"class": request_class,
                                               "outcome": "shed"})
                raise
            try:
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("Transfer-Encoding", "chunked")
                self.send_header(RID_HEADER, rid)
                self.end_headers()
            except BaseException:
                if ticket is not None:
                    service.admission.release(ticket, completed=False)
                raise
            # from here to `closed` the socket is the writer's
            cancel = threading.Event()
            stream = Stream(self.connection, rid, cancel, t0)
            kw.update(cancel=cancel, request_class=request_class,
                      ticket=ticket, deadline=deadline, rid=rid,
                      stream=stream)
            try:
                # generate() owns the ticket's release
                out = service.generate(ids, new_tokens, **kw)
            except BaseException as exc:   # noqa: BLE001 — surfaced as a
                service.streams.finish(stream, error=str(exc))  # stream line
            else:
                service.streams.finish(stream, ids=out.tolist())
            stream.closed.wait()
            if cancel.is_set():
                # a line may have stopped half-way: not a connection to
                # read another request from
                self.close_connection = True

        def do_GET(self):
            if self.path == "/metrics":
                import monitoring
                # what the family's block steps counted on the device
                # since the last scrape (the expert and window counters)
                service.executor.count_stats()
                extra = prom.render_monitoring_snapshot(
                    monitoring.snapshot())
                rec = telemetry.recorder()
                if rec is not None:
                    extra += prom.render_span_digest(rec.digest())
                body = prom.REGISTRY.render(extra=extra).encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif self.path.split("?", 1)[0] == "/debug/spans":
                # per-process span-ring drain (trace_report --fleet
                # federation; ?drain=0 peeks without clearing)
                drain = "drain=0" not in self.path
                self._send(200,
                           fleet_obs.debug_spans_payload(drain=drain))
            elif self.path == "/healthz":
                dead = service.dead is not None
                deg = service.degraded_info
                degraded = False
                if deg is not None:
                    degraded = {"dead_rank": deg["dead_rank"],
                                "since_s": round(time.monotonic()
                                                 - deg["since"], 3),
                                "retry_after": deg["retry_after"],
                                # "degraded" (hole open) vs "healing"
                                # (rank rejoined, restore in progress)
                                "phase": deg.get("phase", "degraded")}
                self._send(503 if dead else 200,
                           {"ok": not dead, "model": model_name,
                            "stages": len(service.pipe.stages),
                            "speculative": service.spec is not None,
                            "executor": "wave",
                            "degraded": degraded,
                            "draining": service.draining,
                            "serving": service.serving_stats(),
                            "flight": service.flight_stats(),
                            # per-peer gray-failure scores when a
                            # peer-health scorer runs in this process
                            # (docs/FAULT_TOLERANCE.md); {} otherwise
                            "peer_health": peer_health.snapshot(),
                            "stats": service.stats()})
            else:
                self._send(404, {"error": "unknown path"})

        def do_POST(self):
            rid = None       # minted for /generate; names error bodies too
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                if self.path == "/debug/dump":
                    # on-demand postmortem bundle (manual trigger — never
                    # cooldown-suppressed): optionally scoped to one rid
                    path = service.dump_postmortem(rid=req.get("rid"))
                    self._send(200, {"path": path,
                                     "written_total":
                                     service.flight.written_total()})
                elif self.path.split("?", 1)[0] == "/debug/profile":
                    if profile_dir is None:
                        self._send(404, {"error": "profiling is off: "
                                         "start with --profile-dir DIR"})
                        return
                    query = urllib.parse.parse_qs(
                        urllib.parse.urlsplit(self.path).query)
                    seconds = float(query.get("seconds", ["3"])[0])
                    if not seconds > 0:
                        raise ValueError("seconds must be > 0")
                    seconds = min(seconds, PROFILE_MAX_SECONDS)
                    try:
                        _profile(profile_dir, seconds)
                    except RuntimeError as exc:
                        # JAX's own one-session rule: another POST, or
                        # whoever else started a session in this process
                        self._send(409, {"error": str(exc)})
                        return
                    self._send(200, {"path": profile_dir,
                                     "seconds": seconds})
                elif self.path == "/degraded":
                    # the failover orchestrator's switch (see module doc):
                    # degraded -> healing -> healed lifecycle
                    if req.get("degraded", True):
                        if req.get("healing"):
                            service.mark_healing()
                        else:
                            # no hint -> DERIVE the Retry-After from the
                            # observed heal history (_derived_retry_after)
                            ra = req.get("retry_after")
                            service.enter_degraded(
                                dead_rank=req.get("dead_rank"),
                                retry_after=(None if ra is None
                                             else float(ra)))
                    else:
                        service.exit_degraded(
                            healed=bool(req.get("healed")),
                            rank=req.get("rank"))
                    self._send(200, {"degraded":
                                     service.degraded_info is not None})
                elif self.path == "/prefix":
                    pid, plen = service.add_prefix(req["ids"])
                    self._send(200, {"prefix_id": pid, "len": plen})
                elif self.path == "/drain":
                    # the router's graceful-drain entry (replica side):
                    # stop admitting, keep finishing; /healthz's
                    # stats.active reports the remaining in-flight work
                    service.begin_drain()
                    self._send(200, {"draining": True,
                                     "active": service.stats().get(
                                         "active", 0)})
                elif self.path == "/kv/export":
                    blob, plen, pages = service.kv_export(req["ids"])
                    self._send(200, {"blob": blob, "tokens_covered": plen,
                                     "pages": pages})
                elif self.path == "/kv/import":
                    pages = service.kv_import(req["ids"], req["blob"])
                    self._send(200, {"installed_pages": pages})
                elif self.path == "/generate":
                    ids = req["ids"]
                    if ids and not isinstance(ids[0], list):
                        ids = [ids]
                    # admission identity: every /generate carries a class
                    # (default interactive) and may carry a deadline
                    # budget in ms from receipt (docs/SERVING.md)
                    request_class = req.get("class", "interactive")
                    if request_class not in REQUEST_CLASSES:
                        raise ValueError(
                            f"unknown request class {request_class!r} "
                            f"(expected one of {sorted(REQUEST_CLASSES)})")
                    deadline_s = None
                    if req.get("deadline_ms") is not None:
                        deadline_s = float(req["deadline_ms"]) / 1e3
                        if deadline_s <= 0:
                            raise ValueError("deadline_ms must be > 0")
                    # the rid arrives on X-PipeEdge-Rid when a router
                    # (or any tracing caller) already minted it — honor
                    # it so the fleet-wide trace stays one tree; mint
                    # HERE only when absent, before any admission
                    # decision: every outcome (200/503/504) names the
                    # same rid, so a loadgen worst-N entry or a 504 body
                    # cross-references the trace and postmortem bundles
                    rid = _header_rid(self.headers) or service.mint_rid()
                    if req.get("speculative"):
                        if req.get("temperature") or req.get("top_k") \
                                or req.get("eos_token") is not None \
                                or req.get("stream"):
                            raise ValueError(
                                "speculative generation is greedy-exact "
                                "whole-rounds; it does not compose with "
                                "sampling/eos/stream")
                        if not service.speculative_allowed():
                            # brownout rung 1 (no_speculative): fall back
                            # to plain greedy — token-identical, but the
                            # serialized draft/verify path stays free
                            out = service.generate(
                                ids, int(req["new_tokens"]),
                                request_class=request_class,
                                deadline_s=deadline_s, rid=rid,
                                temperature=0.0, top_k=0, seed=0,
                                eos_token=None,
                                prefix_id=req.get("prefix_id"))
                        else:
                            out = service.generate_speculative(
                                ids, int(req["new_tokens"]),
                                prefix_id=req.get("prefix_id"),
                                request_class=request_class,
                                deadline_s=deadline_s, rid=rid)
                        self._send(200, {"ids": out.tolist(), "rid": rid},
                                   headers=_rid_headers(rid))
                    else:
                        kw = dict(
                            temperature=float(req.get("temperature", 0.0)),
                            top_k=int(req.get("top_k", 0)),
                            seed=int(req.get("seed", 0)),
                            eos_token=req.get("eos_token"),
                            prefix_id=req.get("prefix_id"))
                        if req.get("stream"):
                            self._stream_generate(
                                ids, int(req["new_tokens"]), kw,
                                request_class, deadline_s, rid=rid)
                        else:
                            out = service.generate(
                                ids, int(req["new_tokens"]),
                                request_class=request_class,
                                deadline_s=deadline_s, rid=rid, **kw)
                            self._send(200, {"ids": out.tolist(),
                                             "rid": rid},
                                       headers=_rid_headers(rid))
                else:
                    self._send(404, {"error": "unknown path"})
            except (KeyError, ValueError, TypeError, IndexError) as exc:
                self._send(400, {"error": str(exc)})
            except AdmissionShed as exc:
                # overload backpressure: the Retry-After is COMPUTED from
                # the observed service rate ("come back when the queue you
                # would join has drained"), not a constant
                self._send(503, {"error": str(exc), "shed": True,
                                 "class": exc.request_class,
                                 "reason": exc.reason, "rid": rid},
                           headers=(("Retry-After",
                                     f"{exc.retry_after:g}"),)
                           + _rid_headers(rid))
            except DeadlineExceeded as exc:
                # the deadline expired while EXECUTING: the executor
                # cancelled it at a decode-step boundary (no Retry-After —
                # re-sending the same budget would expire the same way).
                # The rid cross-references the postmortem bundle this 504
                # just triggered (flight recorder).
                self._send(504, {"error": str(exc),
                                 "deadline_exceeded": True,
                                 "class": exc.request_class, "rid": rid},
                           headers=_rid_headers(rid))
            except ServiceDegraded as exc:
                # a degraded window is transient by contract: tell the
                # client exactly when to come back instead of hanging it
                self._send(503, {"error": str(exc),
                                 "degraded": True,
                                 "dead_rank": exc.dead_rank, "rid": rid},
                           headers=(("Retry-After",
                                     f"{exc.retry_after:g}"),)
                           + _rid_headers(rid))
            except RuntimeError as exc:
                # every 503 carries a Retry-After (docs/SERVING.md audit):
                # even a dead-worker 503 names the best current estimate
                self._send(503, {"error": str(exc)},
                           headers=(("Retry-After",
                                     f"{service.retry_after_hint():g}"),))

    return Handler


def _parse_class_map(pairs, what, parser):
    """`interactive=2.5`-style repeated CLI pairs -> {class: float}."""
    try:
        out = parse_class_map(pairs, what)
    except ValueError as exc:
        parser.error(str(exc))
    return out or None


def _inject_stall(pipe, spec, parser):
    """`--inject-stall STAGE:MS` — wrap every callable of one pipeline
    stage with a fixed sleep. A deterministic, attributable stall for the
    traced-serve smoke: it lands INSIDE that stage's `exec{i}` span, so
    `trace_report --request` must name exactly this stage as the
    dominant stall (the acceptance gate)."""
    import functools
    try:
        stage_s, ms_s = spec.split(":", 1)
        idx, delay_s = int(stage_s), float(ms_s) / 1e3
        st = pipe.stages[idx]
    except (ValueError, IndexError):
        parser.error(f"--inject-stall expects STAGE:MS with STAGE < "
                     f"{len(pipe.stages)}, got {spec!r}")
        return

    def slow(fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            time.sleep(delay_s)
            return fn(*a, **kw)
        return wrapper

    for key, fn in list(st.items()):
        if callable(fn):
            st[key] = slow(fn)
    # the programs the executor makes for this stage later (the step of the
    # rows that stand together, decode_rows.StageRows) are wrapped as made
    st["wrap"] = slow
    print(f"chaos: injecting {ms_s}ms stall into every step of stage "
          f"{idx}", flush=True)


class WorkerSupervisor:
    """Spawns and supervises a fleet of child worker PROCESSES: respawn
    on death with crash-loop backoff and an epoch bump per incarnation.
    Subclasses name the fleet (`LABEL`/`TAG`) and provide the per-rank
    argv/env/ready-line contract — `PrefillWorkerSupervisor` runs the
    prefill fleet of `--disaggregate process`, `ReplicaSupervisor` the
    decode replicas of `--role router`
    (docs/FAULT_TOLERANCE.md lifecycles)."""

    LABEL = "worker"       # human/log name ("prefill worker rank 1 died")
    TAG = "worker"         # stdout tee prefix ("[worker r1] ...")

    RESPAWN_DELAY_S = 0.5
    RESPAWN_BACKOFF_MAX_S = 30.0
    FAST_DEATH_S = 5.0     # an incarnation dying this fast escalates

    def __init__(self, ranks, respawn=True):
        import subprocess
        self._subprocess = subprocess
        self.ranks = tuple(ranks)
        self.respawn = bool(respawn)
        self._procs = {}                  # rank -> Popen
        self._epoch = {r: 0 for r in self.ranks}
        self._ready = {r: threading.Event() for r in self.ranks}
        # crash-loop protection: a worker that dies FAST (startup
        # failure, host OOM) doubles its respawn delay up to the cap —
        # each respawn pays a full interpreter + model build, so a
        # deterministic failure must not thrash the host at 2 Hz; an
        # incarnation that lived a while resets the backoff
        self._backoff = {r: self.RESPAWN_DELAY_S for r in self.ranks}
        self._spawned_at = {r: 0.0 for r in self.ranks}
        self._respawn_after = {r: 0.0 for r in self.ranks}
        # ranks retired by the autoscaler: their epoch records are
        # RETAINED so a future add_rank continues the sequence (+1) and
        # the rejoin stays fenced against every dead incarnation
        self._retired = set()
        self._stop = threading.Event()
        self._lock = make_lock(f"serve.{self.TAG}_sup")
        self._watchers = []
        for r in self.ranks:
            self._spawn(r)
        self._supervisor = threading.Thread(target=self._watch_loop,
                                            daemon=True,
                                            name=f"{self.TAG}-supervisor")
        self._supervisor.start()

    # -- the per-fleet contract (subclasses) -----------------------------

    def _argv(self, rank):
        raise NotImplementedError

    def _env(self, rank):
        env = dict(os.environ)
        # every incarnation carries its epoch: a respawned worker's
        # JOIN/readmission is fenced against its dead predecessor
        env["DCN_EPOCH"] = str(self._epoch[rank])
        return env

    def _is_ready(self, rank, line):
        raise NotImplementedError

    # -- lifecycle -------------------------------------------------------

    def _spawn(self, rank):
        import subprocess
        proc = subprocess.Popen(
            self._argv(rank), env=self._env(rank), text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        with self._lock:
            # stop() may have swept _procs while this Popen was in
            # flight (the respawn/shutdown race): a spawn the shutdown
            # can no longer see must be terminated HERE, not leaked
            if self._stop.is_set():
                proc.terminate()
                return
            self._procs[rank] = proc
            self._spawned_at[rank] = time.monotonic()
        t = threading.Thread(target=self._pump, args=(rank, proc),
                             daemon=True, name=f"{self.TAG}-out-r{rank}")
        t.start()
        # pump threads exit when their worker's stdout closes: prune
        # the dead ones so a long-lived server doesn't accumulate one
        # Thread record per respawn
        self._watchers = [w for w in self._watchers if w.is_alive()]
        self._watchers.append(t)
        print(f"{self.LABEL} rank {rank} spawned "
              f"(pid={proc.pid}, epoch={self._epoch[rank]})", flush=True)

    def _pump(self, rank, proc):
        # tee worker output through the server's stdout (prefixed): the
        # chaos harness and CI key on the workers' chaos/ready lines
        for line in proc.stdout:
            print(f"[{self.TAG} r{rank}] {line}", end="", flush=True)
            if self._is_ready(rank, line):
                self._ready[rank].set()

    def _watch_loop(self):
        dead_pending = set()       # deaths observed, respawn not yet due
        while not self._stop.wait(self.RESPAWN_DELAY_S):
            now = time.monotonic()
            for rank in self.ranks:
                with self._lock:
                    proc = self._procs.get(rank)
                if proc is None or proc.poll() is None:
                    continue
                if rank not in dead_pending:
                    # observe the death ONCE: escalate the backoff only
                    # for fast deaths (crash loop), reset otherwise
                    lived = now - self._spawned_at[rank]
                    if lived < self.FAST_DEATH_S:
                        self._backoff[rank] = min(
                            self.RESPAWN_BACKOFF_MAX_S,
                            self._backoff[rank] * 2)
                    else:
                        self._backoff[rank] = self.RESPAWN_DELAY_S
                    self._respawn_after[rank] = now + self._backoff[rank]
                    dead_pending.add(rank)
                    print(f"{self.LABEL} rank {rank} died "
                          f"(rc={proc.returncode}; respawn backoff "
                          f"{self._backoff[rank]:g}s)", flush=True)
                    if not self.respawn:
                        with self._lock:
                            self._procs.pop(rank, None)
                        continue
                if not self.respawn or self._stop.is_set() \
                        or now < self._respawn_after[rank]:
                    continue
                dead_pending.discard(rank)
                self._ready[rank].clear()
                self._epoch[rank] += 1
                self._spawn(rank)

    def wait_ready(self, timeout=180.0):
        deadline = time.monotonic() + timeout
        for rank in self.ranks:
            if not self._ready[rank].wait(
                    max(0.0, deadline - time.monotonic())):
                raise RuntimeError(
                    f"{self.LABEL} rank {rank} never became ready "
                    f"within {timeout}s")

    def restart(self, rank):
        """Planned restart (the router's drain endgame): terminate the
        incarnation; the watch loop observes the death and respawns it
        with the next epoch — the same path an unplanned death takes,
        so readmission is identical either way."""
        with self._lock:
            proc = self._procs.get(rank)
        if proc is not None and proc.poll() is None:
            proc.terminate()

    # -- autoscale membership (docs/FAULT_TOLERANCE.md autoscale) --------

    def _on_add_rank(self, rank):
        """Subclass hook: provision per-rank resources (a port, an
        argv slot) BEFORE the new rank's first spawn."""

    def add_rank(self, rank=None):
        """Autoscale scale-out: bring a new rank into the supervised
        set, preferring the lowest retired rank id. A resurrected rank
        continues its epoch sequence (+1), so its join is fenced
        against every dead incarnation exactly like a respawn; a
        brand-new rank starts at epoch 0. Returns the rank spawned."""
        with self._lock:
            if rank is None:
                spare = sorted(self._retired)
                rank = spare[0] if spare else \
                    (max(self._epoch) + 1 if self._epoch else 0)
            if rank in self.ranks:
                raise ValueError(f"rank {rank} is already active")
            self._retired.discard(rank)
            if rank in self._epoch:
                self._epoch[rank] += 1
            else:
                self._epoch[rank] = 0
            self._ready[rank] = threading.Event()
            self._backoff[rank] = self.RESPAWN_DELAY_S
            self._spawned_at[rank] = 0.0
            self._respawn_after[rank] = 0.0
            self._on_add_rank(rank)
            self.ranks = tuple(list(self.ranks) + [rank])
        self._spawn(rank)
        return rank

    def retire_rank(self, rank):
        """Autoscale scale-in endgame: take `rank` out of the
        supervised set WITHOUT respawn and terminate its incarnation.
        The proc record is popped under the lock BEFORE the terminate,
        so the watch loop can never observe the death and resurrect
        it. Epoch records are retained (see add_rank)."""
        with self._lock:
            if rank not in self.ranks:
                return False
            self.ranks = tuple(r for r in self.ranks if r != rank)
            proc = self._procs.pop(rank, None)
            self._ready[rank].clear()
            self._retired.add(rank)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except self._subprocess.TimeoutExpired:
                proc.kill()
        print(f"{self.LABEL} rank {rank} retired "
              f"(epoch={self._epoch[rank]})", flush=True)
        return True

    def snapshot(self):
        with self._lock:
            return {str(r): {"pid": p.pid, "epoch": self._epoch[r],
                             "alive": p.poll() is None}
                    for r, p in self._procs.items()}

    def stop(self):
        self._stop.set()
        self._supervisor.join(timeout=5)
        with self._lock:
            procs = list(self._procs.values())
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except self._subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5)


class PrefillWorkerSupervisor(WorkerSupervisor):
    """The prefill fleet of `--disaggregate process`
    (tools/prefill_worker.py ranks 1..N of the ship plane's DCN world).
    A worker that dies — crash, OOM, chaos kill — is respawned with
    DCN_EPOCH incremented, so its JOIN clears the decode side's death
    fence and the fleet readmits it (docs/FAULT_TOLERANCE.md
    disaggregated serving lifecycle). Chaos: PIPEEDGE_PREFILL_CHAOS (a
    DCN_CHAOS spec) arms deterministic faults in ONE worker's env
    (PIPEEDGE_PREFILL_CHAOS_RANK, default 1) for the first incarnation
    only — respawns come up clean, exactly like the restart@K:MS
    contract."""

    LABEL = "prefill worker"
    TAG = "prefill"

    def __init__(self, worker_cmd, ranks, respawn=True, http_ports=None):
        self._cmd = list(worker_cmd)      # without rank; appended per rank
        # rank -> observability HTTP port (each worker serves /metrics +
        # /debug/spans there, so the fleet collector and trace_report
        # --fleet reach prefill processes too)
        self._http_ports = dict(http_ports or {})
        super().__init__(ranks, respawn=respawn)

    def _argv(self, rank):
        argv = [sys.executable] + self._cmd[:1] + [str(rank)] \
            + self._cmd[1:]
        port = self._http_ports.get(rank)
        if port:
            argv += ["--http-port", str(port)]
        return argv

    def snapshot(self):
        out = super().snapshot()
        for rank, port in self._http_ports.items():
            rec = out.get(str(rank))
            if rec is not None:
                rec["http_url"] = f"http://127.0.0.1:{port}"
        return out

    def _env(self, rank):
        env = super()._env(rank)
        chaos = os.getenv("PIPEEDGE_PREFILL_CHAOS")
        chaos_rank = int(os.getenv("PIPEEDGE_PREFILL_CHAOS_RANK", "1"))
        if chaos and rank == chaos_rank and self._epoch[rank] == 0:
            env["DCN_CHAOS"] = chaos
        return env

    def _is_ready(self, rank, line):
        # exact machine line only: a bare substring ("ready") would
        # also match e.g. "...already initialized" warnings from
        # the model build and release wait_ready() mid-build
        return line.startswith(f"prefill worker rank {rank} ready")


class ReplicaSupervisor(WorkerSupervisor):
    """The decode replicas behind `--role router`: each rank is a full
    `serve.py --role replica` process on its own port. A replica that
    dies respawns with the next epoch after crash-loop backoff; the
    router's health polls readmit it once it proves itself (the
    registry's readmit confirmation — docs/FAULT_TOLERANCE.md replica
    lifecycle). `restart(rank)` is the drain endgame: planned
    detach rides the same death-observation path."""

    LABEL = "decode replica"
    TAG = "replica"

    def __init__(self, base_cmd, host, ports, respawn=True):
        self._base_cmd = list(base_cmd)
        self._host = host
        self._ports = list(ports)
        super().__init__(range(len(ports)), respawn=respawn)

    def _argv(self, rank):
        return [sys.executable] + self._base_cmd + [
            "--host", self._host, "--port", str(self._ports[rank])]

    def _is_ready(self, rank, line):
        # the replica's own "serving ... on HOST:PORT" line; the port
        # makes it rank-unique
        return (line.startswith("serving ")
                and f" on {self._host}:{self._ports[rank]}" in line)

    def _on_add_rank(self, rank):
        # a resurrected rank reuses its old port (the listener is
        # gone — nothing holds it); a brand-new rank gets a fresh one
        while len(self._ports) <= rank:
            self._ports += _free_ports(1, self._host)

    def url_of(self, rank):
        return f"http://{self._host}:{self._ports[rank]}"


def _free_ports(n, host="127.0.0.1"):
    import socket as socket_mod
    socks = [socket_mod.create_server((host, 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def make_router_handler(router, model_name, collector=None,
                        autoscaler=None):
    """HTTP surface of `--role router`: the same endpoint shapes a
    single replica serves (clients need no code change), backed by the
    DecodeRouter instead of a local pipeline. `collector` (a
    FleetCollector) backs GET /fleet — the one aggregated scrape
    surface across router + replicas + prefill workers. `autoscaler`
    (an AutoscaleRunner) adds the capacity controller's snapshot to
    /healthz and /fleet — the block the chaos harness polls for
    decision counts."""

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"      # chunked transfer needs 1.1

        def log_message(self, *a):      # quiet server
            pass

        def _send(self, code, obj, headers=()):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for name, value in headers:
                self.send_header(name, value)
            self.end_headers()
            self.wfile.write(body)

        def _chunk(self, obj):
            data = json.dumps(obj).encode() + b"\n"
            self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            self.wfile.flush()

        def do_GET(self):
            if self.path == "/metrics":
                body = prom.REGISTRY.render().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif self.path == "/fleet":
                if collector is None:
                    self._send(503, {"error": "fleet collector disabled "
                                              "(--fleet-scrape-interval "
                                              "0)"},
                               headers=(("Retry-After", "5"),))
                else:
                    snap = collector.fleet_snapshot()
                    if autoscaler is not None:
                        snap["autoscale"] = \
                            autoscaler.controller.snapshot()
                    self._send(200, snap)
            elif self.path.split("?", 1)[0] == "/debug/spans":
                # the router's own span ring (trace_report --fleet
                # federation; ?drain=0 peeks without clearing)
                drain = "drain=0" not in self.path
                self._send(200,
                           fleet_obs.debug_spans_payload(drain=drain))
            elif self.path == "/healthz":
                code, body = router.healthz()
                body["model"] = model_name
                if autoscaler is not None:
                    body["autoscale"] = \
                        autoscaler.controller.snapshot()
                headers = ((("Retry-After", "1"),) if code == 503
                           else ())
                self._send(code, body, headers=headers)
            else:
                self._send(404, {"error": f"unknown path {self.path}"})

        def do_POST(self):
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                if self.path == "/generate":
                    if req.get("stream"):
                        self._stream(req)
                        return
                    status, body, headers = router.dispatch(req)
                    self._send(status, body, headers=headers)
                elif self.path == "/prefix":
                    pid, plen = router.register_prefix(req["ids"])
                    self._send(200, {"prefix_id": pid, "len": plen})
                elif self.path == "/drain":
                    out = router.drain_replica(
                        req["replica"],
                        migrate=bool(req.get("migrate", True)))
                    self._send(200 if out.get("drained") else 409, out)
                else:
                    self._send(404, {"error": f"unknown path {self.path}"})
            except (KeyError, ValueError, TypeError, IndexError) as exc:
                self._send(400, {"error": str(exc)})
            except RuntimeError as exc:
                self._send(503, {"error": str(exc)},
                           headers=(("Retry-After", "1"),))

        def _stream(self, req):
            """Relay a streaming generation: the router's generator
            owns failover; this method only moves lines to the socket
            (a mid-stream replica death is invisible here beyond the
            suppressed replay latency)."""
            streaming = False
            it = router.stream(req)
            for item in it:
                if item[0] == "status":
                    _, code, headers = item
                    if code == 200:
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/x-ndjson")
                        self.send_header("Transfer-Encoding", "chunked")
                        for name, value in headers:
                            # the identity echo (X-PipeEdge-Rid /
                            # -Replica) rides the stream headers too
                            self.send_header(name, value)
                        self.end_headers()
                        streaming = True
                    else:
                        nxt = next(it, None)
                        body = (nxt[1] if nxt is not None
                                and nxt[0] == "line" else {})
                        self._send(code, body, headers=headers)
                        return
                else:
                    try:
                        self._chunk(item[1])
                    except OSError:
                        # client went away: closing the generator tears
                        # down the upstream replica connection too
                        it.close()
                        return
            if streaming:
                try:
                    self.wfile.write(b"0\r\n\r\n")
                    self.wfile.flush()
                except OSError:
                    pass

    return Handler


def _run_router(args):
    """`--role router` entry: spawn/adopt the replica fleet, start the
    health poller, serve the routed HTTP surface. Model-free — the
    router never imports jax or loads weights."""
    from pipeedge_tpu.serving.router import DecodeRouter, RouterPolicy
    policy = RouterPolicy(
        poll_interval_s=args.router_poll_interval,
        health_timeout_s=args.router_health_timeout,
        request_timeout_s=args.route_timeout,
        route_retries=args.route_retries,
        hedge_ms=args.hedge_ms,
        drain_timeout_s=args.drain_timeout)
    supervisor = None
    if args.replica_addrs:
        replicas = {}
        for i, addr in enumerate(args.replica_addrs.split(",")):
            addr = addr.strip()
            replicas[f"r{i}"] = (addr if addr.startswith("http")
                                 else f"http://{addr}")
    else:
        ports = _free_ports(args.replicas, args.host)
        base_cmd = [
            os.path.abspath(__file__), "--role", "replica",
            "-m", args.model_name,
            "--max-len", str(args.max_len), "-t", args.dtype,
            "--kv-bits", str(args.kv_bits),
            "--attend-floor", str(args.attend_floor),
            "--max-prefixes", str(args.max_prefixes),
            "--queue-capacity", str(args.queue_capacity),
            "--kv-pages", str(args.kv_pages),
            "--kv-page-size", str(args.kv_page_size),
            "--chunked-prefill", str(args.chunked_prefill),
            "--governor-interval", str(args.governor_interval),
            "--brownout-queue-high", str(args.brownout_queue_high),
            "--brownout-queue-low", str(args.brownout_queue_low),
            "--brownout-p95-high", str(args.brownout_p95_high),
            "--brownout-p95-low", str(args.brownout_p95_low),
            "--brownout-dwell-up", str(args.brownout_dwell_up),
            "--brownout-dwell-down", str(args.brownout_dwell_down),
            "--brownout-clamp-tokens", str(args.brownout_clamp_tokens),
            "--brownout-clamp-chunk", str(args.brownout_clamp_chunk),
            "--slo-objective", str(args.slo_objective),
            "--slo-burn-fast", str(args.slo_burn_fast),
            "--slo-burn-slow", str(args.slo_burn_slow),
            "--slo-burn-threshold", str(args.slo_burn_threshold)]
        if args.partition:
            base_cmd += ["-pt", args.partition]
        if args.max_active is not None:
            base_cmd += ["--max-active", str(args.max_active)]
        if args.prefill_budget is not None:
            base_cmd += ["--prefill-budget", str(args.prefill_budget)]
        if args.step_join:
            base_cmd += ["--step-join"]
        if args.no_admission:
            base_cmd += ["--no-admission"]
        if args.no_brownout:
            base_cmd += ["--no-brownout"]
        if args.draft_model:
            base_cmd += ["--draft-model", args.draft_model,
                         "--gamma", str(args.gamma)]
        for kvp in (args.class_rate or []):
            base_cmd += ["--class-rate", kvp]
        for kvp in (args.class_deadline or []):
            base_cmd += ["--class-deadline", kvp]
        if args.inject_stall:
            base_cmd += ["--inject-stall", args.inject_stall]
        supervisor = ReplicaSupervisor(
            base_cmd, args.host, ports,
            respawn=not args.no_replica_respawn)
        replicas = {f"r{i}": f"http://{args.host}:{port}"
                    for i, port in enumerate(ports)}
    router = DecodeRouter(replicas, policy=policy, supervisor=supervisor)
    # the router is a peer process of the fleet observatory: span ring
    # for /debug/spans, flight recorder for slo_burn postmortems
    telemetry.configure(rank=0)
    router_flight = flight.configure(rank=0,
                                     out_dir=args.postmortem_dir)
    collector = None
    if args.fleet_scrape_interval > 0:
        def _on_breach(cls, burn):
            router_flight.note("slo_burn_breach", rid=None,
                               request_class=cls,
                               burn=round(burn, 3))
            router_flight.maybe_dump(
                "slo_burn",
                context={"class": cls, "burn_rate": round(burn, 4),
                         "window": "short",
                         "objective": args.slo_objective,
                         "threshold": args.slo_burn_threshold,
                         "fleet": router.registry.snapshot()})
        burn = fleet_obs.BurnRateEngine(
            objective=args.slo_objective,
            fast_window_s=args.slo_burn_fast,
            slow_window_s=args.slo_burn_slow,
            threshold=args.slo_burn_threshold,
            on_breach=_on_breach)
        collector = fleet_obs.FleetCollector(
            router.scrape_targets,
            interval_s=args.fleet_scrape_interval,
            history=args.fleet_history,
            burn=burn)
    autoscaler = None
    if args.autoscale != "off":
        # the closed capacity loop (serving/autoscale.py): signals come
        # from the fleet collector's aggregated scrape, actuators are
        # the supervisor (spawn with the next epoch) + the router's
        # drain-without-respawn path. advise mode runs the identical
        # loop but only logs — the A/B control arm.
        from pipeedge_tpu.serving import autoscale as autoscale_mod
        apol = autoscale_mod.CapacityPolicy(
            min_size=args.autoscale_min,
            max_size=args.autoscale_max,
            confirm=args.autoscale_confirm,
            cooldown_s=args.autoscale_cooldown,
            dwell_up_s=args.autoscale_dwell_up,
            dwell_down_s=args.autoscale_dwell_down,
            queue_high=args.autoscale_queue_high,
            queue_low=args.autoscale_queue_low,
            burn_high=args.autoscale_burn_high,
            burn_low=args.autoscale_burn_low)

        def _fleet_size():
            return len(router.registry.names())

        def _plan_capacity(direction, cur, target):
            # the dry-run: an un-runnable move renders as `held`
            if supervisor is None:
                return {"ok": False,
                        "reason": "static fleet (--replica-addrs)"}
            if direction == "up":
                return {"ok": True, "direction": "up", "to": target}
            snap = router.registry.snapshot()
            healthy = [n for n, rec in snap.items()
                       if rec["state"] == "healthy"]
            if len(healthy) < 2:
                return {"ok": False,
                        "reason": "no healthy survivor to absorb "
                                  "the drain"}
            # newest healthy replica leaves first (LIFO): the warmest
            # caches stay with the longest-lived replicas
            victim = max(healthy,
                         key=lambda n: int(n[1:]) if n[1:].isdigit()
                         else -1)
            return {"ok": True, "direction": "down", "victim": victim,
                    "to": target}

        def _apply_capacity(plan):
            if plan["direction"] == "up":
                rank = supervisor.add_rank()
                name = f"r{rank}"
                url = supervisor.url_of(rank)
                router.add_replica(name, url, rank=rank)
                print(f"autoscale_spawn replica={name} rank={rank} "
                      f"epoch={supervisor.snapshot()[str(rank)]['epoch']} "
                      f"url={url}", flush=True)
            else:
                victim = plan["victim"]
                out = router.remove_replica(victim)
                rank = out.get("rank")
                if rank is not None:
                    supervisor.retire_rank(rank)
                print(f"autoscale_drain replica={victim} rank={rank} "
                      f"migrated={out.get('migrated_prefixes', 0)}",
                      flush=True)

        controller = autoscale_mod.CapacityController(
            apol, mode=args.autoscale, size_fn=_fleet_size,
            plan_fn=_plan_capacity, apply_fn=_apply_capacity,
            label="replicas")

        def _signals():
            fleet = collector.fleet_snapshot()
            return autoscale_mod.signals_from_fleet(fleet, _fleet_size())

        autoscaler = autoscale_mod.AutoscaleRunner(
            controller, _signals, interval_s=args.autoscale_interval)
    if supervisor is not None:
        for i, name in enumerate(replicas):
            router.bind_rank(name, i)
        supervisor.wait_ready(timeout=600.0)
    router.start()
    if collector is not None:
        collector.start()
    if autoscaler is not None:
        autoscaler.start()
        print(f"autoscale mode={args.autoscale} "
              f"min={args.autoscale_min} max={args.autoscale_max} "
              f"confirm={args.autoscale_confirm} "
              f"cooldown={args.autoscale_cooldown:g}", flush=True)
    server = _HTTPServer(
        (args.host, args.port),
        make_router_handler(router, args.model_name,
                            collector=collector,
                            autoscaler=autoscaler))
    print(f"serving router ({len(replicas)} replicas) on "
          f"{args.host}:{args.port}", flush=True)
    try:
        server.serve_forever()
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        if collector is not None:
            collector.stop()
        router.stop()
        if supervisor is not None:
            supervisor.stop()


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-m", "--model-name", default="gpt2")
    p.add_argument("-pt", "--partition", default=None)
    p.add_argument("--max-len", default=1024, type=int)
    p.add_argument("-t", "--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--kv-bits", default=0, type=int, choices=[0, 8])
    p.add_argument("--attend-floor", default=64, type=int)
    p.add_argument("--int8-decode-attend", default=None,
                   choices=["0", "1", "2", "auto"],
                   help="int8-KV decode attention kernel opt-in for the "
                        "serving pipeline (needs --kv-bits 8): 0 = XLA "
                        "dequant route, 1 = v1 kernel, 2 = v2, auto = "
                        "width-policy v2. Default: PIPEEDGE_INT8_DECODE_"
                        "ATTEND, else on (auto) when the int8 compute "
                        "path is enabled (docs/QUANTIZATION.md)")
    p.add_argument("--draft-model", default=None,
                   help="enable speculative generation: requests with "
                        '"speculative": true run greedy draft/verify '
                        "rounds against this (smaller, same-vocabulary) "
                        "model — token-identical to plain greedy")
    p.add_argument("--gamma", default=4, type=int,
                   help="speculative draft lookahead per round")
    p.add_argument("--max-active", default=None, type=int)
    p.add_argument("--max-prefixes", default=8, type=int,
                   help="LRU bound on registered prompt prefixes (each "
                        "handle retains full max_len KV buffers; with "
                        "--kv-pages only the token lists are stored — "
                        "the prefix trie owns the KV)")
    p.add_argument("--port", default=8321, type=int)
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address for the HTTP server and the "
                        "ship/lease listeners (default loopback; use "
                        "a NIC address or 0.0.0.0 for non-loopback "
                        "replicas)")
    # -- routed decode fleet (docs/SERVING.md router topology) ----------
    p.add_argument("--role", default="single",
                   choices=["single", "router", "replica"],
                   help="single: one decode process serving directly "
                        "(the historical mode); router: a model-free "
                        "front-end that health-checks and routes across "
                        "N decode replicas (spawned and supervised, or "
                        "external via --replica-addrs); replica: a "
                        "decode process behind a router (same serving "
                        "surface as single, plus drain/migration)")
    p.add_argument("--replicas", default=2, type=int,
                   help="decode replica processes the router spawns "
                        "and supervises (ignored with --replica-addrs)")
    p.add_argument("--replica-addrs", default=None,
                   metavar="HOST:PORT,...",
                   help="route across EXTERNAL replicas at these "
                        "addresses instead of spawning any (no respawn "
                        "supervision — lifecycle is the operator's)")
    p.add_argument("--no-replica-respawn", action="store_true",
                   help="do not respawn dead decode replicas (default: "
                        "respawn with crash-loop backoff + epoch bump "
                        "and readmit after clean health polls)")
    p.add_argument("--router-poll-interval", default=0.5, type=float,
                   help="seconds between /healthz polls per replica")
    p.add_argument("--router-health-timeout", default=2.0, type=float,
                   help="health-poll timeout; a slow poll scores as "
                        "degraded, a failed one as a miss")
    p.add_argument("--route-timeout", default=120.0, type=float,
                   help="per-attempt request timeout at the router")
    p.add_argument("--route-retries", default=2, type=int,
                   help="re-route attempts to a DIFFERENT replica after "
                        "a connect failure or mid-stream death")
    p.add_argument("--hedge-ms", default=0.0, type=float,
                   help="tail hedging for non-streaming interactive "
                        "requests: if the primary replica has not "
                        "answered within this many ms, race a second "
                        "replica and keep the first answer (0 = off)")
    p.add_argument("--drain-timeout", default=60.0, type=float,
                   help="seconds POST /drain waits for a replica's "
                        "in-flight requests before migrating its "
                        "prefix pages anyway")
    # -- closed-loop capacity (docs/FAULT_TOLERANCE.md autoscale) -------
    p.add_argument("--autoscale", default="off",
                   choices=["off", "advise", "auto"],
                   help="(router) closed-loop capacity control over the "
                        "supervised replica fleet: scale-out spawns a "
                        "replica with the next epoch (warm-up gated "
                        "before it takes traffic), scale-in drains + "
                        "migrates KV prefixes then retires the process. "
                        "advise = run the identical decision loop but "
                        "only log (the A/B control arm); auto = act")
    p.add_argument("--autoscale-min", default=1, type=int,
                   help="replica floor the autoscaler never drains below")
    p.add_argument("--autoscale-max", default=2, type=int,
                   help="replica ceiling it never spawns above")
    p.add_argument("--autoscale-confirm", default=3, type=int,
                   help="consecutive same-direction observation windows "
                        "before a decision is eligible (one hot scrape "
                        "moves nothing)")
    p.add_argument("--autoscale-cooldown", default=10.0, type=float,
                   metavar="S",
                   help="seconds between decisions; each direction "
                        "REVERSAL doubles the effective cooldown "
                        "(flap damper, capped at 8x)")
    p.add_argument("--autoscale-interval", default=1.0, type=float,
                   metavar="S", help="governor tick period")
    p.add_argument("--autoscale-dwell-up", default=0.0, type=float,
                   metavar="S",
                   help="seconds up-pressure must persist before "
                        "scale-out (on top of --autoscale-confirm)")
    p.add_argument("--autoscale-dwell-down", default=5.0, type=float,
                   metavar="S",
                   help="seconds calm must persist before scale-in")
    p.add_argument("--autoscale-queue-high", default=4.0, type=float,
                   help="summed admission queue depth PER REPLICA that "
                        "counts as up pressure")
    p.add_argument("--autoscale-queue-low", default=0.5, type=float,
                   help="per-replica queue depth below which the fleet "
                        "counts as calm (dead band against queue-high)")
    p.add_argument("--autoscale-burn-high", default=1.0, type=float,
                   help="short-window SLO burn rate that counts as up "
                        "pressure")
    p.add_argument("--autoscale-burn-low", default=0.25, type=float,
                   help="burn rate below which the fleet counts as calm")
    # -- paged KV plane + disaggregation (docs/SERVING.md) --------------
    p.add_argument("--kv-pages", default=0, type=int,
                   help="enable the paged KV plane: N fixed-size pages "
                        "per stage shared by every request (page tables "
                        "+ cross-request prefix trie); admission then "
                        "runs on a KV TOKEN budget of N x --kv-page-size "
                        "instead of max_active slots. 0 = dense "
                        "per-request cache slots (the historical mode)")
    p.add_argument("--kv-page-size", default=16, type=int,
                   help="cache positions per KV page")
    p.add_argument("--chunked-prefill", default=0, type=int, metavar="N",
                   help="split prompt passes longer than N tokens into "
                        "N-token chunks interleaved with decode steps "
                        "at every executor step boundary (needs "
                        "--kv-pages; bounds decode-step latency under "
                        "long-prompt bursts). 0 = run-to-completion "
                        "prefill (the historical mode)")
    p.add_argument("--prefill-budget", default=None, type=int,
                   metavar="TOKENS",
                   help="prompt tokens the executor may start per "
                        "decode step when chunking (default: the chunk "
                        "size — one chunk per step)")
    p.add_argument("--step-join", action="store_true",
                   help="re-drive the admission queue at every decode-"
                        "step boundary, so queued requests join mid-"
                        "generation instead of at the next completion")
    p.add_argument("--disaggregate", default="off",
                   choices=["off", "local", "wire", "process"],
                   help="split serving into a prefill fleet and a decode "
                        "fleet (needs --kv-pages): prompt passes run on "
                        "a DEDICATED pipeline and ship finished KV pages "
                        "into the decode executor — 'local' hands arrays "
                        "over in-process, 'wire' pushes real bytes "
                        "through the v2 codec + a loopback socket "
                        "(see --kv-ship-bits), 'process' spawns REAL "
                        "separate prefill worker processes over DCN "
                        "sockets with the fault-tolerant lease/ack ship "
                        "protocol (retry, re-dispatch, colocated "
                        "fallback — docs/FAULT_TOLERANCE.md)")
    p.add_argument("--prefill-ranks", default=1, type=int,
                   help="worker processes of --disaggregate process "
                        "(leases re-dispatch across them on faults)")
    p.add_argument("--prefill-lease-timeout", default=30.0, type=float,
                   help="seconds a dispatched prompt pass may go "
                        "unacked before it re-dispatches")
    p.add_argument("--prefill-attempts", default=3, type=int,
                   help="total lease dispatches per prompt before the "
                        "request degrades to colocated prefill")
    p.add_argument("--no-prefill-respawn", action="store_true",
                   help="do not respawn dead prefill workers (default: "
                        "respawn with DCN_EPOCH+1 and readmit via JOIN)")
    p.add_argument("--prefill-heartbeat-interval", default=1.0,
                   type=float,
                   help="ship-plane heartbeat interval (0 disables; "
                        "catches hung workers whose sockets stay open)")
    p.add_argument("--kv-ship-bits", default=0, type=int, choices=[0, 8],
                   help="quantize shipped KV pages on the wire (int8 "
                        "block-scaled, 4x fewer bytes; 0 = exact — the "
                        "token-parity setting)")
    p.add_argument("--prefill-concurrency", default=2, type=int,
                   help="in-flight prompt passes the prefill fleet runs "
                        "concurrently")
    # -- overload protection (docs/SERVING.md) --------------------------
    p.add_argument("--no-admission", action="store_true",
                   help="disable the SLO-aware admission plane (requests "
                        "block in executor backpressure like pre-serving "
                        "builds; deadlines still propagate)")
    p.add_argument("--queue-capacity", default=64, type=int,
                   help="bound on the EDF admission queue; overflow sheds "
                        "the latest-deadline waiter with 503 + Retry-After")
    p.add_argument("--class-rate", action="append", metavar="CLASS=RPS",
                   help="per-class sustained token-bucket admit rate "
                        "(repeatable; default: unlimited)")
    p.add_argument("--class-deadline", action="append",
                   metavar="CLASS=SECONDS",
                   help="per-class DEFAULT deadline budget applied when a "
                        "request carries no deadline_ms (repeatable)")
    p.add_argument("--no-brownout", action="store_true",
                   help="disable the watermark-driven brownout ladder")
    p.add_argument("--brownout-queue-high", default=8, type=int)
    p.add_argument("--brownout-queue-low", default=1, type=int)
    p.add_argument("--brownout-p95-high", default=2.0, type=float,
                   help="windowed request-latency p95 (s) above which the "
                        "ladder steps up")
    p.add_argument("--brownout-p95-low", default=0.5, type=float)
    p.add_argument("--brownout-dwell-up", default=0.5, type=float,
                   help="seconds the hot condition must persist per "
                        "step up (hysteresis)")
    p.add_argument("--brownout-dwell-down", default=2.0, type=float)
    p.add_argument("--brownout-clamp-tokens", default=16, type=int,
                   help="new_tokens clamp at brownout level >= 2")
    p.add_argument("--brownout-clamp-chunk", default=0, type=int,
                   metavar="TOKENS",
                   help="chunked-prefill chunk-size clamp at brownout "
                        "level >= 2 (0 = lever unarmed; only applies "
                        "with --chunked-prefill)")
    p.add_argument("--governor-interval", default=0.25, type=float,
                   help="brownout governor tick (s)")
    p.add_argument("--trace-spans", default=None, metavar="OUT",
                   help="record request/stage spans and write a Perfetto-"
                        "loadable trace JSON to OUT on shutdown "
                        "(tools/trace_report.py analyzes it)")
    p.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="enable POST /debug/profile?seconds=N: one JAX "
                        "profiler session at a time (409 while one runs, "
                        f"N capped at {PROFILE_MAX_SECONDS:g}) written "
                        "to DIR, the serving loop's spans named on the "
                        "device trace's clock (docs/OBSERVABILITY.md)")
    p.add_argument("--postmortem-dir", default=None, metavar="DIR",
                   help="directory for flight-recorder postmortem bundles "
                        "(default: env PIPEEDGE_POSTMORTEM_DIR or "
                        "./postmortems); bundles are written on 504s, "
                        "sheds, failover, SLO breach, and POST /debug/dump")
    p.add_argument("--fleet-scrape-interval", default=1.0, type=float,
                   metavar="S",
                   help="(router) period of the fleet collector's "
                        "/metrics scrape across replicas and prefill "
                        "workers — feeds GET /fleet and the SLO burn-"
                        "rate engine (<= 0 disables; /fleet then 503s)")
    p.add_argument("--fleet-history", default=120, type=int,
                   help="(router) scrape samples retained per target "
                        "(the /fleet rate window is bounded by "
                        "history * scrape interval)")
    p.add_argument("--slo-objective", default=0.99, type=float,
                   help="per-class SLO objective (good-request fraction) "
                        "the burn-rate engine budgets against")
    p.add_argument("--slo-burn-fast", default=30.0, type=float,
                   metavar="S",
                   help="short burn-rate window (s) — breaching "
                        "threshold here triggers one slo_burn "
                        "postmortem bundle per episode")
    p.add_argument("--slo-burn-slow", default=300.0, type=float,
                   metavar="S", help="long burn-rate window (s)")
    p.add_argument("--slo-burn-threshold", default=10.0, type=float,
                   help="short-window burn rate that counts as a breach "
                        "(10 = burning a 30d budget in ~3d)")
    p.add_argument("--inject-stall", default=None, metavar="STAGE:MS",
                   help="chaos hook (tests/CI only): sleep MS ms inside "
                        "every step of pipeline stage STAGE — the "
                        "deterministic stall the traced-serve smoke "
                        "asserts trace_report --request can name")
    args = p.parse_args()

    # parse-time composition checks — BEFORE any model build, so a bad
    # flag pair fails in milliseconds with both flags named, not after
    # minutes of weight loading (and never as a bare mid-construction
    # refusal from _Service)
    if args.disaggregate != "off" and not args.kv_pages:
        p.error("--disaggregate needs --kv-pages (shipped KV lands in "
                "the paged pool)")
    if args.chunked_prefill < 0:
        p.error("--chunked-prefill must be >= 0")
    if args.chunked_prefill and not args.kv_pages:
        p.error("--chunked-prefill needs --kv-pages (chunk waves write "
                "prompt spans at an offset into the request's page "
                "table; dense cache slots have no span-at-offset path)")
    if args.prefill_budget is not None and not args.chunked_prefill:
        p.error("--prefill-budget only applies with --chunked-prefill")
    if args.prefill_budget is not None and args.prefill_budget < 1:
        p.error("--prefill-budget must be >= 1")
    if args.role == "router":
        if args.disaggregate != "off":
            p.error("--role router does not compose with --disaggregate "
                    "yet (run disaggregation inside each replica is a "
                    "scoped follow-up; see docs/SERVING.md)")
        if args.replica_addrs is None and args.replicas < 1:
            p.error("--replicas must be >= 1 (or pass --replica-addrs)")
        if args.hedge_ms < 0:
            p.error("--hedge-ms must be >= 0")
        if args.route_retries < 0:
            p.error("--route-retries must be >= 0")
        if args.autoscale != "off":
            if args.replica_addrs is not None:
                p.error("--autoscale needs a SUPERVISED fleet (it "
                        "spawns and retires replica processes); "
                        "--replica-addrs fleets are the operator's "
                        "lifecycle")
            if args.fleet_scrape_interval <= 0:
                p.error("--autoscale needs the fleet collector "
                        "(--fleet-scrape-interval > 0) — its scrape is "
                        "the controller's signal plane")
            if not 1 <= args.autoscale_min <= args.autoscale_max:
                p.error("need 1 <= --autoscale-min <= --autoscale-max")
            if args.autoscale_confirm < 1:
                p.error("--autoscale-confirm must be >= 1")
            if args.autoscale_interval <= 0:
                p.error("--autoscale-interval must be > 0")
    elif args.replica_addrs is not None:
        p.error("--replica-addrs only applies with --role router")
    elif args.autoscale != "off":
        p.error("--autoscale only applies with --role router (runtime "
                "--rounds fleets get the pipeline-level half via "
                "runtime.py --autoscale-ranks)")

    if args.role == "router":
        # the router is a model-free proxy: no jax, no weights — it
        # routes, health-checks, drains, and migrates
        return _run_router(args)

    from pipeedge_tpu.utils import enable_compile_cache, report_devices
    enable_compile_cache()
    # spans are always on in serving processes: GET /debug/spans drains
    # the ring for trace_report --fleet federation without pre-arming
    # (the `startup` spans of this function among them).
    # --trace-spans keeps controlling only the shutdown trace dump.
    telemetry.configure(rank=0)
    # pipeedge_jax_compiles_total: "nothing compiles under load" as a number
    prom.count_jax_compiles()
    with telemetry.startup("backend"):
        report_devices()
    import jax.numpy as jnp

    from pipeedge_tpu.parallel.decode import build_decode_pipeline

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    partition = None
    if args.partition:
        nums = [int(x) for x in args.partition.split(",")]
        partition = list(zip(nums[::2], nums[1::2]))
    pipe = build_decode_pipeline(
        args.model_name, partition, max_len=args.max_len, dtype=dtype,
        cache_bits=args.kv_bits, attend_floor=args.attend_floor,
        int8_decode_attend=args.int8_decode_attend)
    if args.inject_stall:
        _inject_stall(pipe, args.inject_stall, p)
    spec = None
    if args.draft_model:
        if args.kv_bits:
            p.error("--draft-model does not compose with --kv-bits (int8 "
                    "span verification is not bit-identical to serial "
                    "int8 steps)")
        from pipeedge_tpu.parallel.speculative import SpeculativeDecoder
        d_pipe = build_decode_pipeline(
            args.draft_model, None, max_len=args.max_len, dtype=dtype,
            attend_floor=args.attend_floor)
        spec = SpeculativeDecoder(pipe, d_pipe, gamma=args.gamma)
    prefill_fleet = None
    prefill_supervisor = None
    ship_ctx = None
    if args.disaggregate == "process":
        # REAL separate prefill processes over DCN sockets (this process
        # is rank 0 of the ship plane; workers are ranks 1..N). The
        # lease/ack protocol makes the split survivable: ship timeout /
        # CRC failure / worker death re-dispatch or degrade to colocated
        # prefill, and dead workers respawn with DCN_EPOCH+1 and JOIN
        # back in (docs/FAULT_TOLERANCE.md disaggregated serving)
        from pipeedge_tpu.comm import dcn
        from pipeedge_tpu.kv import RemotePrefillFleet
        world = 1 + args.prefill_ranks
        addrs = [(args.host, port)
                 for port in _free_ports(world, args.host)]
        addr_arg = ",".join(f"{h}:{port}" for h, port in addrs)
        worker_cmd = [
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "prefill_worker.py"),
            str(world), "--dcn-addrs", addr_arg,
            "-m", args.model_name, "--max-len", str(args.max_len),
            "-t", args.dtype, "--attend-floor", str(args.attend_floor),
            "--heartbeat-interval",
            str(args.prefill_heartbeat_interval)]
        if args.partition:
            worker_cmd += ["-pt", args.partition]
        # per-worker observability listeners (GET /metrics, /healthz,
        # /debug/spans): the replica's /healthz exposes each worker's
        # http_url, and the router's fleet collector scrapes them
        pf_http = dict(zip(range(1, world),
                           _free_ports(args.prefill_ranks, args.host)))
        prefill_supervisor = PrefillWorkerSupervisor(
            worker_cmd, ranks=range(1, world),
            respawn=not args.no_prefill_respawn,
            http_ports=pf_http)
        ship_ctx = dcn.DistDcnContext(world, 0, addrs)
        ship_ctx.init()
        prefill_supervisor.wait_ready()
        prefill_fleet = RemotePrefillFleet(
            ship_ctx, ranks=range(1, world), dtype=dtype,
            ship_bits=args.kv_ship_bits,
            lease_timeout_s=args.prefill_lease_timeout,
            max_attempts=args.prefill_attempts,
            max_concurrent=max(1, args.prefill_concurrency),
            heartbeat_interval=args.prefill_heartbeat_interval)
    elif args.disaggregate != "off":
        from pipeedge_tpu.kv import PrefillFleet
        # a DEDICATED pipeline: its prompt passes never contend with the
        # decode executor's stage programs for host dispatch order
        prefill_pipe = build_decode_pipeline(
            args.model_name, partition, max_len=args.max_len, dtype=dtype,
            attend_floor=args.attend_floor)
        prefill_fleet = PrefillFleet(
            prefill_pipe, path=args.disaggregate,
            ship_bits=args.kv_ship_bits,
            max_concurrent=args.prefill_concurrency)

    from pipeedge_tpu.analysis import lockdep
    if args.trace_spans or lockdep.enabled():
        # SIGTERM must unwind through the finally below (the default
        # handler would kill the process before the trace — or the
        # PIPEEDGE_LOCKDEP atexit report — is written)
        import signal
        signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))
    with telemetry.startup("service"):
        service = _Service(pipe, max_active=args.max_active,
                           max_prefixes=args.max_prefixes, spec=spec,
                           edge_itemsize=2 if args.dtype == "bfloat16" else 4,
                           admission_enabled=not args.no_admission,
                           queue_capacity=args.queue_capacity,
                           class_rates=_parse_class_map(
                               args.class_rate, "--class-rate", p),
                           class_deadlines_s=_parse_class_map(
                               args.class_deadline, "--class-deadline", p),
                           brownout_enabled=not args.no_brownout,
                           brownout_marks=Watermarks(
                               queue_high=args.brownout_queue_high,
                               queue_low=args.brownout_queue_low,
                               p95_high_s=args.brownout_p95_high,
                               p95_low_s=args.brownout_p95_low,
                               dwell_up_s=args.brownout_dwell_up,
                               dwell_down_s=args.brownout_dwell_down),
                           clamp_new_tokens=args.brownout_clamp_tokens,
                           governor_interval=args.governor_interval,
                           postmortem_dir=args.postmortem_dir,
                           kv_pages=args.kv_pages,
                           kv_page_size=args.kv_page_size,
                           prefill_fleet=prefill_fleet,
                           prefill_supervisor=prefill_supervisor,
                           chunked_prefill=args.chunked_prefill,
                           step_join=args.step_join,
                           prefill_budget=args.prefill_budget,
                           clamp_chunk_tokens=args.brownout_clamp_chunk,
                           slo_objective=args.slo_objective,
                           slo_burn_fast=args.slo_burn_fast,
                           slo_burn_slow=args.slo_burn_slow,
                           slo_burn_threshold=args.slo_burn_threshold)
        if prefill_fleet is not None and hasattr(prefill_fleet,
                                                 "flight_note"):
            # ship-plane faults (lease timeouts, zombie drops, worker
            # deaths/readmissions) land in the flight recorder's event ring
            prefill_fleet.flight_note = service.flight.note
        server = _HTTPServer((args.host, args.port),
                             make_handler(service, args.model_name,
                                          profile_dir=args.profile_dir))
    print(f"serving {args.model_name} ({len(pipe.stages)} stages) on "
          f"{args.host}:{args.port}", flush=True)
    try:
        server.serve_forever()
    finally:
        service.stop()
        if ship_ctx is not None:
            ship_ctx.shutdown()
        if args.trace_spans and telemetry.recorder() is not None:
            from pipeedge_tpu.telemetry import chrome_trace
            chrome_trace.dump_trace(telemetry.recorder().snapshot(),
                                    args.trace_spans)


if __name__ == "__main__":
    main()
