"""Paged-KV execution backend for the serving executor.

`ContinuousBatcher` (parallel/batcher.py) drives per-request
stage-steps; this backend replaces its dense per-request cache slots
with page-table indirection over the shared pool:

- **admit**: charge `ceil((prompt + new_tokens) / page_size)` pages per
  batch row (power-of-two bucketed to bound compiled cache shapes),
  walk the prefix trie for whole-page prompt reuse (B==1 requests), or
  install a prefill fleet's SHIPPED KV rows (kv/ship.py) so the decode
  fleet never runs a prompt pass at all.
- **run_stage**: gather the request's cache view from the page arena,
  dispatch the UNCHANGED compiled stage program (prefill / span / step —
  exactly `_run_stage`'s semantics, same `stage`/`exec{i}` spans), then
  scatter back only the pages the step actually wrote AND that the
  request privately owns — shared prefix pages are physically
  immutable.
- **release**: drop the request's page references; completed prompts'
  full pages were published to the trie at the end of their prompt
  pass, so the NEXT request with that prefix reuses them.

Numerics: the gathered view is `[n_blocks, B, pages * page_size, ...]`
instead of the dense `[.., max_len, ..]` — positions past the window
were fully masked in the dense path (exact softmax zeros), so the paged
path is TOKEN-IDENTICAL to the dense executor and to solo
`DecodePipeline.generate` runs for fp caches (tests/test_kv_plane.py
pins this); int8 caches carry the same quantization caveat as
`precompute_prefix` reuse.

Thread model: page/trie accounting locks live in pool/prefix; the
arena's read-modify-write (gather -> program dispatch -> scatter) is
serialized under one "kv.arena" lock — dispatch is async, so the hold
is host-side only.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..telemetry import metrics as prom
from ..utils.threads import make_lock
from .pool import KvPagePool, pages_for
from .prefix import PrefixTrie


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class PagedKvBackend:
    """The executor's cache provider: page tables instead of dense slots.

    `share_prefixes` arms the trie (single-row requests only — lockstep
    multi-row prompts have per-row token content); `bucket_pages` rounds
    each request's page span up to a power of two so the per-stage
    decode programs compile per page-count BUCKET, not per exact prompt
    length (the attend-window `attend_bucket` idea applied to the cache
    shape)."""

    def __init__(self, pipe, n_pages: int, page_size: int = 16,
                 pool: Optional[KvPagePool] = None,
                 trie: Optional[PrefixTrie] = None,
                 share_prefixes: bool = True,
                 bucket_pages: bool = True,
                 registry: Optional[prom.Registry] = None):
        self.pipe = pipe
        self.pool = pool if pool is not None else KvPagePool(
            pipe, n_pages, page_size, registry=registry)
        self.page_size = self.pool.page_size
        self.trie = trie if trie is not None else (
            PrefixTrie(self.pool, registry=registry)
            if share_prefixes else None)
        if self.trie is not None:
            self.pool.set_evict_hook(self.trie.evict_cold)
        self.bucket_pages = bool(bucket_pages)
        self._arena_lock = make_lock("kv.arena")
        self._n_stages = len(pipe.stages)

    # -- sizing -----------------------------------------------------------

    def pages_needed(self, prompt_len: int, new_tokens: int,
                     batch: int = 1) -> int:
        per_row = pages_for(prompt_len + new_tokens, self.page_size)
        if self.bucket_pages:
            per_row = min(_next_pow2(per_row),
                          pages_for(self.pipe.max_len, self.page_size))
        return per_row * batch

    def tokens_needed(self, prompt_len: int, new_tokens: int,
                      batch: int = 1) -> int:
        """The admission token charge (pages x page_size: what the
        request actually reserves, bucketing included)."""
        return self.pages_needed(prompt_len, new_tokens,
                                 batch) * self.page_size

    def can_admit(self, req) -> bool:
        """Whether `admit` would succeed right now (free + evictable
        cold pages cover the request) — the wave batcher's pending-queue
        gate, so a too-big head request pends instead of raising."""
        need = self.pages_needed(req.prompt_len, req.new_tokens,
                                 req.ids.shape[0])
        free = self.pool.free_pages
        if free >= need:
            return True
        cold = self.trie.cold_pages() if self.trie is not None else 0
        return free + cold >= need

    def check_admittable(self, req) -> None:
        """Reject at SUBMIT time what admission could never take: a
        hand-passed prefix handle (the trie replaces them), or a page
        reservation exceeding the whole pool — the paged analogue of
        `validate_capacity`'s up-front max_len check. Without this the
        wave batcher's pending queue would wedge behind a head whose
        `can_admit` can never become true (or its serve loop would die
        on the deferred ValueError instead of the submitter)."""
        if getattr(req, "prefix", None) is not None:
            raise ValueError(
                "paged KV replaces hand-passed prefix handles (the "
                "prefix trie shares prompts automatically); submit the "
                "full prompt instead")
        need = self.pages_needed(req.prompt_len, req.new_tokens,
                                 req.ids.shape[0])
        if need > self.pool.n_pages:
            raise ValueError(
                f"request needs {need} KV page(s) "
                f"({req.ids.shape[0]} row(s) x prompt {req.prompt_len} "
                f"+ {req.new_tokens} new tokens at page_size "
                f"{self.page_size}); the pool holds {self.pool.n_pages}")

    # -- admission --------------------------------------------------------

    def admit(self, req) -> Tuple[str, object]:
        """Seed the request's page tables; returns `(kind, data)` for
        its first stage-0 dispatch: ("prefill", ids) for a fresh prompt,
        ("span", suffix_ids) when the trie matched a prefix, ("step",
        token) when shipped KV was installed (the prompt pass already
        happened on the prefill fleet), or ("done", None) when the
        shipped first token already completes the request."""
        if getattr(req, "prefix", None) is not None:
            raise ValueError(
                "paged KV replaces hand-passed prefix handles (the "
                "prefix trie shares prompts automatically); submit the "
                "full prompt instead")
        batch, prompt_len = req.ids.shape[0], req.prompt_len
        per_row = self.pages_needed(prompt_len, req.new_tokens)
        shipped = getattr(req, "shipped", None)
        tokens = (np.asarray(req.ids)[0].tolist() if batch == 1
                  and self.trie is not None else None)
        shared_pids: List[int] = []
        if shipped is None and tokens is not None:
            shared_pids = self.trie.lookup(tokens,
                                           max_tokens=prompt_len - 1)
        shared = len(shared_pids)
        private: List[List[int]] = []
        try:
            for _ in range(batch):
                private.append(self.pool.alloc(per_row - shared))
        except BaseException:
            for row in private:
                self.pool.release(row)
            if shared_pids:
                self.pool.release(shared_pids)
            raise
        table = np.asarray(
            [shared_pids + row for row in private], np.int32)
        req.kvstate = {
            "table": table, "shared": shared,
            "shared_len": shared * self.page_size,
            "owned": shared_pids + [p for row in private for p in row],
            "tokens": tokens, "published": False,
        }
        # leak audit: the pool's owner ledger mirrors this request's
        # page references from the instant they exist, so a submitter
        # that dies anywhere past this point (install failure path
        # included) is reclaimable by the orphan sweep
        self.pool.adopt(req.rid, req.kvstate["owned"])
        if shipped is not None:
            try:
                return self._install_shipped(req, shipped)
            except BaseException:
                # a malformed handle must not leak the pages just
                # charged (the executor rolls back its slot, not ours)
                self.release(req)
                raise
        if shared:
            return "span", req.ids[:, shared * self.page_size:]
        return "prefill", req.ids

    def _install_shipped(self, req, handle) -> Tuple[str, object]:
        """Write a prefill fleet's shipped KV rows into this request's
        pages and pick the first token from the shipped last-stage
        logits — the decode-fleet side of disaggregation (kv/ship.py
        moved the bytes; this lands them)."""
        ks0 = req.kvstate
        if ks0.get("install_result") is not None:
            # idempotence fence: a second install (retried/zombie ship
            # delivered twice above the lease fence) must neither
            # re-scatter pages nor re-append the first token — return
            # the first install's decision unchanged
            return ks0["install_result"]
        plen = int(handle["prompt_len"])
        rows = handle["stage_rows"]
        if plen != req.prompt_len:
            raise ValueError(f"shipped KV covers {plen} prompt tokens; "
                             f"request prompt is {req.prompt_len}")
        if len(rows) != self._n_stages:
            raise ValueError(f"shipped KV has {len(rows)} stages; this "
                             f"pipeline has {self._n_stages}")
        ks = req.kvstate
        touched = list(range(pages_for(plen, self.page_size)))
        batch = req.ids.shape[0]
        with telemetry.span("kv", "install", rid=str(req.rid)):
            with self._arena_lock:
                for i in range(self._n_stages):
                    view = self.pool.gather(i, ks["table"])
                    if set(rows[i]) != set(view):
                        raise ValueError(
                            f"shipped KV leaves {sorted(rows[i])} do not "
                            f"match this pipeline's cache leaves "
                            f"{sorted(view)} (cache_bits mismatch?)")
                    for name, arr in rows[i].items():
                        arr = jnp.asarray(arr).astype(view[name].dtype)
                        if arr.shape[1] != batch:
                            raise ValueError(
                                f"shipped KV batch {arr.shape[1]} != "
                                f"request batch {batch}")
                        view[name] = view[name].at[:, :, :plen].set(arr)
                    self.pool.scatter(
                        i, ks["table"], view,
                        [(b, j) for b in range(batch) for j in touched])
        if self.trie is not None and tokens_publishable(req):
            self._publish(req)
        # the prefill fleet ships LOGITS, not a token: the pick stays on
        # the decode side with the request's own rng discipline, so
        # disaggregated tokens are identical to colocated ones
        token, req.step_ids, req.rng = req.pick(
            jnp.asarray(handle["logits"])[:, None], req.rng)
        req.tokens.append(token)    # the executor hands it on (`_emit`)
        done = len(req.tokens) >= req.new_tokens
        if not done and req.eos_token is not None:
            hit = np.asarray(token) == req.eos_token
            req.rows_done = hit
            done = bool(hit.all())
        result = ("done", None) if done else ("step", req.step_ids)
        ks["install_result"] = result
        return result

    # -- the stage-step indirection --------------------------------------

    def _touched_pages(self, kind: str, req, span: int) -> range:
        ks = req.kvstate
        if kind == "prefill":
            lo, hi = 0, req.prompt_len
        elif kind == "span":
            lo, hi = ks["shared_len"], req.prompt_len
        elif kind == "chunk":
            # chunked prefill: only this chunk's slice of the prompt
            # was written (earlier chunks already scattered theirs)
            lo, hi = req.chunk_off, req.chunk_off + span
        else:
            lo, hi = req.pos, req.pos + 1
        return range(lo // self.page_size,
                     pages_for(hi, self.page_size))

    def run_stage(self, i: int, req, data, kind: str):
        """One stage-step through page-table indirection — the paged
        analogue of `batcher._run_stage` (same spans, same program
        dispatch, device placement included)."""
        st = self.pipe.stages[i]
        ks = req.kvstate
        batch = req.ids.shape[0]
        span = data.shape[1] if kind in ("prefill", "span", "chunk") else 1
        with telemetry.span("stage", f"exec{i}", stage=i,
                            rid=str(req.rid)):
            writes = [(b, j) for b in range(batch)
                      for j in self._touched_pages(kind, req, span)
                      if j >= ks["shared"]]
            if st["device"] is not None:
                data = jax.device_put(data, st["device"])
            with self._arena_lock:
                cache = self.pool.gather(i, ks["table"])
                if kind == "prefill":
                    out, cache = st["prefill"](st["params"], data, cache)
                elif kind == "span":
                    out, cache = self.pipe._decode_step(
                        st, data, cache, ks["shared_len"], span=span)
                elif kind == "chunk":
                    # one slice of a chunked prompt pass: a span at the
                    # chunk's absolute offset (batcher._run_stage's rule)
                    out, cache = self.pipe._decode_step(
                        st, data, cache, req.chunk_off, span=span)
                else:
                    out, cache = self.pipe._decode_step(st, data, cache,
                                                        req.pos)
                self.pool.scatter(i, ks["table"], cache, writes)
        # trie publish waits for the prompt pass to COMPLETE: a single
        # prefill/span, or the FINAL chunk of a chunked pass (publishing
        # a half-written prompt would serve garbage pages to sharers)
        if i == self._n_stages - 1 and self.trie is not None \
                and (kind in ("prefill", "span")
                     or (kind == "chunk" and req.chunk_final)) \
                and tokens_publishable(req):
            with telemetry.span("exec", "publish", stage=i,
                                rid=str(req.rid)):
                self._publish(req)
        return out

    def _publish(self, req) -> None:
        """Prompt pass complete on every stage: hand the prompt's FULL
        pages to the trie for cross-request reuse (partial tail pages
        stay private — their owner's decode steps keep writing them)."""
        ks = req.kvstate
        ks["published"] = True
        full = req.prompt_len // self.page_size
        if full <= ks["shared"]:
            return          # nothing new beyond the already-shared pages
        self.trie.insert(ks["tokens"][:full * self.page_size],
                         ks["table"][0][:full].tolist())

    # -- prefix migration (router drain — docs/FAULT_TOLERANCE.md) -------

    def export_prefix(self, tokens, bits: int = 0):
        """Snapshot the trie's cached pages for this prompt prefix as
        wire-v2 ship frames (kv/ship.py) — the router's drain path ships
        these to a survivor replica instead of re-prefilling there.
        Returns `(frames, tokens_covered, n_pages)` or `None` when the
        trie holds nothing for the prefix (or is unarmed / the cache is
        int8 — quantized caches don't ship exactly)."""
        if self.trie is None:
            return None
        toks = [int(t) for t in tokens]
        pids = self.trie.lookup(toks, max_tokens=len(toks))
        if not pids:
            return None
        try:
            plen = len(pids) * self.page_size
            table = np.asarray([pids], np.int32)
            caches = []
            with telemetry.span("kv", "export", mb=None):
                with self._arena_lock:
                    for i in range(self._n_stages):
                        view = self.pool.gather(i, table)
                        if set(view) != {"k", "v"}:
                            return None       # int8 cache: not shippable
                        caches.append(view)
                from . import ship
                # prefix export carries no sampling decision — the
                # logits slot is a placeholder the importer ignores
                frames = ship.encode_kv_ship(
                    caches, plen, np.zeros((1, 1), np.float32), bits=bits)
            return frames, plen, len(pids)
        finally:
            # lookup took one reference per matched page for us; the
            # trie's own retention references keep the pages cached
            self.pool.release(pids)

    def install_prefix(self, tokens, handle) -> int:
        """Land a peer replica's exported prefix into this pool + trie
        (the receive side of `export_prefix`): alloc pages, scatter the
        shipped rows, publish to the trie. Idempotent — a prefix the
        trie already covers installs zero pages. Returns pages
        installed."""
        if self.trie is None:
            raise ValueError("prefix install needs the prefix trie "
                             "(share_prefixes)")
        toks = [int(t) for t in tokens]
        plen = int(handle["prompt_len"])
        rows = handle["stage_rows"]
        if plen % self.page_size or plen > len(toks) or plen <= 0:
            raise ValueError(
                f"shipped prefix covers {plen} tokens; expected a "
                f"positive multiple of page_size {self.page_size} "
                f"within the {len(toks)}-token prefix")
        if len(rows) != self._n_stages:
            raise ValueError(f"shipped prefix has {len(rows)} stages; "
                             f"this pipeline has {self._n_stages}")
        toks = toks[:plen]
        if self.trie.peek(toks, max_tokens=plen) >= plen:
            return 0        # already cached here: nothing to install
        n = plen // self.page_size
        pids = self.pool.alloc(n)
        try:
            table = np.asarray([pids], np.int32)
            writes = [(0, j) for j in range(n)]
            with telemetry.span("kv", "import"):
                with self._arena_lock:
                    for i in range(self._n_stages):
                        view = self.pool.gather(i, table)
                        if set(rows[i]) != set(view):
                            raise ValueError(
                                f"shipped prefix leaves "
                                f"{sorted(rows[i])} do not match this "
                                f"pipeline's cache leaves "
                                f"{sorted(view)}")
                        for name, arr in rows[i].items():
                            arr = jnp.asarray(arr).astype(
                                view[name].dtype)
                            view[name] = view[name].at[
                                :, :, :plen].set(arr)
                        self.pool.scatter(i, table, view, writes)
            # insert adds the trie's retention refs for NEW nodes; pages
            # duplicating an existing node stay ours alone and die with
            # the release below
            self.trie.insert(toks, pids)
        except BaseException:
            self.pool.release(pids)
            raise
        self.pool.release(pids)     # drop the alloc ref; trie refs live on
        return n

    # -- completion / pressure -------------------------------------------

    def release(self, req) -> None:
        ks = getattr(req, "kvstate", None)
        if not ks:
            return
        req.kvstate = None
        # claim-then-release through the owner ledger: if the orphan
        # sweep already reclaimed this request (we ARE the death it
        # raced), disown returns None and there is nothing left to drop
        pids = self.pool.disown(req.rid)
        if pids is not None:
            self.pool.release(pids)

    def shared_prompt_tokens(self, tokens) -> int:
        """How many leading prompt tokens the trie could serve from
        shared pages right now (no references taken — a routing probe;
        the binding lookup happens at admission)."""
        if self.trie is None or tokens is None:
            return 0
        return self.trie.peek(tokens, max_tokens=len(tokens) - 1)

    def sweep_orphans(self, live_rids) -> int:
        """Reclaim pages whose owning request is no longer live (the
        periodic leak audit — a shipper/submitter death mid-transfer
        must strand zero pages). `live_rids` is the executor's live
        request-id set; returns pages reclaimed."""
        return self.pool.sweep_leaked(live_rids)

    def evict_cold_all(self) -> int:
        """Drop EVERY cold cached prefix page (the brownout
        `evict_cold_pages` rung's sweep). 0 when no trie is armed."""
        if self.trie is None:
            return 0
        return self.trie.evict_cold(None)

    def snapshot(self) -> dict:
        s = {"pool": self.pool.stats()}
        if self.trie is not None:
            s["prefix"] = self.trie.stats()
        return s


def tokens_publishable(req) -> bool:
    """Whether this request's prompt can feed the trie: sharing armed,
    single-row, host tokens captured, not already published."""
    ks = getattr(req, "kvstate", None)
    return (ks is not None and not ks["published"]
            and ks["tokens"] is not None)
