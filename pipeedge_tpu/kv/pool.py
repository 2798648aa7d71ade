"""Paged KV pool: fixed-size token pages behind per-request page tables.

The serving executor historically gave every request PRIVATE dense
per-stage cache slots sized for `max_len` tokens (`DecodePipeline.
_fresh_caches`), so concurrency was bounded by SLOTS — a 6-token
interactive request held the same KV memory as a 1024-token one, and a
prompt prefix shared by a thousand requests was prefixed a thousand
times unless the caller hand-passed a `precompute_prefix` handle. This
module is the memory half of ROADMAP item 2's paged KV plane:

- **One page arena per stage**, preallocated: page `p` of stage `i` is
  a `[n_blocks, page_size, ...]` slice of each cache leaf (K, V, and —
  for int8 caches — their scale/shift rows), so a page always means the
  same `page_size` token positions on EVERY stage and one page-id list
  describes a request fleet-wide.
- **Page tables, not slots**: a request holds `ceil((prompt + new_tokens)
  / page_size)` pages per batch row; admission charges tokens, not
  slots, so short requests pack densely and concurrency is bounded by
  the pool's TOKEN capacity (serving/admission.py's token budget).
- **Refcounted sharing**: pages are refcounted, so the prefix trie
  (kv/prefix.py) can retain a finished prompt's pages for cross-request
  reuse — a later request with the same prompt prefix references the
  SAME arena pages instead of re-prefilling them.
- **Static shapes preserved**: the executor materializes a request's
  cache view by a gather over the page axis and write back touched
  pages with a scatter (kv/backend.py); the compiled stage programs are
  exactly `DecodePipeline`'s, shaped `[n_blocks, B, pages * page_size,
  ...]` — one program per page-count bucket, no dynamic shapes.

Eviction: when the free list runs dry, `alloc` calls the registered
evict hook (the trie's cold-page eviction) before failing — and the
brownout ladder's `evict_cold_pages` rung (serving/brownout.py) calls
it proactively, reclaiming cached-but-idle prefix pages before any
request is shed.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.stage_cache import init_cache
from ..telemetry import metrics as prom
from ..utils.threads import make_rlock


class PoolExhausted(RuntimeError):
    """The pool cannot supply the requested pages — even after cold-page
    eviction. The serving layer's token-budget admission exists to make
    this unreachable; hitting it from a raw executor is backpressure."""

    def __init__(self, need: int, free: int, capacity: int):
        super().__init__(
            f"KV page pool exhausted: need {need} page(s), {free} free "
            f"of {capacity}")
        self.need = need
        self.free = free
        self.capacity = capacity


def pages_for(tokens: int, page_size: int) -> int:
    """Pages covering `tokens` cache positions (ceil division)."""
    if tokens <= 0:
        return 0
    return -(-int(tokens) // int(page_size))


class KvPagePool:
    """Preallocated per-stage page arenas + one global page-id space.

    `pipe` supplies the per-stage cache geometry (block counts, KV head
    layout, dtype, cache_bits) — arena leaves mirror `init_cache`'s
    leaves with the batch axis replaced by the page axis. Sharded
    pipelines (tp/sp/ep meshes) are refused: their caches are
    device-sharded pytrees whose page gather/scatter would silently
    gather across shards (the paged plane covers the host-driven
    serving pipeline, like the executor it backs).

    Thread model: page accounting (free list, refcounts) lives under one
    condition ("kv.pool"); `release` notifies so a blocking `alloc` can
    wait for completions. Arena LEAVES are swapped functionally
    (`arr.at[...].set`) by `scatter` — the caller (kv/backend.py)
    serializes same-stage mutations under its arena lock.
    """

    def __init__(self, pipe, n_pages: int, page_size: int = 16,
                 registry: Optional[prom.Registry] = None):
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if getattr(pipe, "mesh", None) is not None \
                or getattr(pipe, "ep_mesh", None) is not None \
                or getattr(pipe, "tp_ep_mesh", None) is not None \
                or getattr(pipe, "sp_degree", 1) != 1:
            raise ValueError(
                "paged KV covers the host-driven pipeline; tp/ep/sp mesh "
                "pipelines keep their sharded dense caches")
        self.pipe = pipe
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        # arena leaves per stage: template leaf [L, 1, page, ...] ->
        # arena [P, L, page, ...] (batch axis dropped; page axis leads)
        self._arena: List[Dict[str, jax.Array]] = []
        for st in pipe.stages:
            tmpl = init_cache(pipe.cfg, st["n_blocks"], 1, page_size,
                              pipe.dtype, cache_bits=pipe.cache_bits)
            leaves = {}
            for name, leaf in tmpl.items():
                shape = (self.n_pages, leaf.shape[0]) + leaf.shape[2:]
                arr = jnp.zeros(shape, leaf.dtype)
                if st["device"] is not None:
                    arr = jax.device_put(arr, st["device"])
                leaves[name] = arr
            self._arena.append(leaves)
        self._lock = make_rlock("kv.pool")
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._refs: Dict[int, int] = {}
        # owner ledger (leak audit, docs/FAULT_TOLERANCE.md): the page
        # references a live REQUEST holds, keyed by its id — what the
        # periodic sweep reconciles against executor liveness so a
        # submitter that died mid-ship can never strand its pages
        self._owners: Dict[str, List[int]] = {}
        self._evict_hook: Optional[Callable[[int], int]] = None
        reg = prom.REGISTRY if registry is None else registry
        self.m_pages = reg.gauge(
            "pipeedge_kv_pages",
            "KV page pool accounting by state (total / free); occupancy "
            "= 1 - free/total (docs/SERVING.md paged KV plane)")
        self.m_pages.set(self.n_pages, state="total")
        self.m_pages.set(self.n_pages, state="free")
        self.m_evicted = reg.counter(
            "pipeedge_kv_pages_evicted_total",
            "cold prefix pages reclaimed from the trie (allocation "
            "pressure or the brownout evict_cold_pages rung)")
        self.m_evicted.declare()
        self.m_leaked = reg.counter(
            "pipeedge_kv_pages_leaked_total",
            "page references reclaimed by the orphan sweep: their "
            "owning request was no longer live (submitter/shipper died "
            "between page charge and release — "
            "docs/FAULT_TOLERANCE.md disaggregated serving)")
        self.m_leaked.declare()

    # -- accounting -------------------------------------------------------

    @property
    def tokens_capacity(self) -> int:
        """Total cache positions the pool can hold (the admission token
        budget's natural value)."""
        return self.n_pages * self.page_size

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def set_evict_hook(self, hook: Optional[Callable[[int], int]]) -> None:
        """`hook(need) -> freed` reclaims cold pages (the prefix trie's
        eviction); called OUTSIDE the pool lock on allocation pressure."""
        self._evict_hook = hook

    def refcount(self, pid: int) -> int:
        with self._lock:
            return self._refs.get(pid, 0)

    def refcounts(self) -> Dict[int, int]:
        """One locked snapshot of every page's refcount — the trie's
        cold-page walks take this ONCE instead of a pool-lock round
        trip per node (kv/prefix.py)."""
        with self._lock:
            return dict(self._refs)

    def alloc(self, n: int) -> List[int]:
        """Take `n` fresh pages (refcount 1 each). On a dry free list the
        evict hook runs first; a pool still short then raises
        `PoolExhausted` (the executor asks `can_admit` first and keeps
        the request pending — parallel/batcher.py)."""
        if n <= 0:
            return []
        if n > self.n_pages:
            raise PoolExhausted(n, self.free_pages, self.n_pages)
        while True:
            with self._lock:
                if len(self._free) >= n:
                    pids = [self._free.pop() for _ in range(n)]
                    for p in pids:
                        self._refs[p] = 1
                    self.m_pages.set(len(self._free), state="free")
                    return pids
                short = n - len(self._free)
            hook = self._evict_hook
            if hook is not None and hook(short) > 0:
                continue            # eviction freed something: retry
            with self._lock:
                if len(self._free) >= n:
                    continue        # a release raced us: retry the take
                raise PoolExhausted(n, len(self._free), self.n_pages)

    def share(self, pids: Sequence[int]) -> None:
        """Add one reference to each page (prefix reuse / trie retention)."""
        with self._lock:
            for p in pids:
                if self._refs.get(p, 0) <= 0:
                    raise ValueError(f"share of unallocated page {p}")
                self._refs[p] += 1

    def release(self, pids: Sequence[int], evicted: bool = False) -> None:
        """Drop one reference per page; refcount 0 returns the page to
        the free list."""
        freed = 0
        with self._lock:
            for p in pids:
                r = self._refs.get(p, 0)
                if r <= 0:
                    raise ValueError(f"release of unallocated page {p}")
                if r == 1:
                    del self._refs[p]
                    self._free.append(p)
                    freed += 1
                else:
                    self._refs[p] = r - 1
            if freed:
                self.m_pages.set(len(self._free), state="free")
        if evicted and freed:
            self.m_evicted.inc(freed)

    # -- owner ledger + orphan sweep (leak audit) -------------------------

    def adopt(self, owner, pids: Sequence[int]) -> None:
        """Record `owner` (a request id) as holding one reference to
        each page in `pids` — the set `release`/`sweep_leaked` will
        drop. Exactly ONE of the two ever drops it: `disown` is the
        atomic claim."""
        with self._lock:
            self._owners[str(owner)] = list(pids)

    def disown(self, owner) -> Optional[List[int]]:
        """Claim `owner`'s page references for release. None = already
        claimed (the request's own release path and the orphan sweep
        race benignly: whoever pops the ledger entry does the release,
        the other sees None and does nothing)."""
        with self._lock:
            return self._owners.pop(str(owner), None)

    def sweep_leaked(self, live_owners) -> int:
        """Reconcile the owner ledger against executor liveness: drop
        the page references of every owner no longer live (a submitter
        or shipper that died between page charge and release). Safe
        against completion races — the executor lists a request as live
        BEFORE charging pages and releases pages BEFORE delisting it, so
        a ledger entry whose owner is not live is genuinely orphaned —
        but ONLY if the ledger is observed FIRST and liveness SECOND:
        pass `live_owners` as a CALLABLE for live systems (invoked
        after the ledger snapshot; returning None aborts the sweep) so
        a request admitted between the two reads can never be taken
        for dead. A plain set is accepted for offline callers with no
        concurrent admissions. Returns pages reference-dropped
        (pipeedge_kv_pages_leaked_total counts them; /healthz surfaces
        the running total)."""
        with self._lock:
            owners = list(self._owners)
        if callable(live_owners):
            live_owners = live_owners()
            if live_owners is None:     # liveness snapshot raced; skip
                return 0
        live = {str(o) for o in live_owners}
        dead = [o for o in owners if o not in live]
        leaked = 0
        for owner in dead:
            pids = self.disown(owner)
            if pids:
                self.release(pids)
                leaked += len(pids)
        if leaked:
            self.m_leaked.inc(leaked)
        return leaked

    def stats(self) -> dict:
        with self._lock:
            free = len(self._free)
            shared = sum(1 for r in self._refs.values() if r > 1)
            owners = len(self._owners)
        return {"pages_total": self.n_pages, "pages_free": free,
                "page_size": self.page_size,
                "pages_shared": shared,
                "occupancy": round(1.0 - free / self.n_pages, 4),
                "pages_evicted_total": int(self.m_evicted.value()),
                "owners": owners,
                "leaked": int(self.m_leaked.value())}

    # -- the gather/scatter indirection ----------------------------------

    def gather(self, stage: int, table: np.ndarray) -> Dict[str, jax.Array]:
        """Materialize a request's stage-`stage` cache view from its page
        table `[B, n]` -> cache leaves `[L, B, n * page_size, ...]` (the
        exact layout `DecodePipeline`'s stage programs consume)."""
        ids = jnp.asarray(np.asarray(table, np.int32))
        out = {}
        for name, arr in self._arena[stage].items():
            g = arr[ids]                       # [B, n, L, page, ...]
            g = jnp.moveaxis(g, 2, 0)          # [L, B, n, page, ...]
            out[name] = g.reshape(g.shape[0], g.shape[1], -1,
                                  *g.shape[4:])
        return out

    def scatter(self, stage: int, table: np.ndarray,
                cache: Dict[str, jax.Array],
                writes: Sequence[Tuple[int, int]]) -> None:
        """Write the view pages named by `writes` — `(row, page_col)`
        pairs into `table` — back into the stage arena. Only a request's
        PRIVATE, TOUCHED pages are written (kv/backend.py computes the
        set), so shared prefix pages are physically immutable."""
        if not writes:
            return
        table = np.asarray(table)
        b_idx = np.asarray([b for b, _ in writes], np.int32)
        j_idx = np.asarray([j for _, j in writes], np.int32)
        pids = jnp.asarray(table[b_idx, j_idx].astype(np.int32))
        n = table.shape[1]
        arena = self._arena[stage]
        for name, arr in arena.items():
            v = cache[name]                    # [L, B, n*page, ...]
            v = v.reshape(v.shape[0], v.shape[1], n, self.page_size,
                          *v.shape[3:])
            v = jnp.moveaxis(v, 0, 2)          # [B, n, L, page, ...]
            pieces = v[jnp.asarray(b_idx), jnp.asarray(j_idx)]
            arena[name] = arr.at[pids].set(pieces)
