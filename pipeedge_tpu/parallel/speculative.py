"""Speculative decoding: a draft pipeline proposes, the target verifies.

NEW capability beyond the reference (whose model list is encoder-only;
SURVEY.md §2.4 — no decode subsystem at all). TPU-first design:

- **Greedy-exact**: output is token-identical to `target.generate(...,
  temperature=0)` for fp caches — verification accepts exactly the draft
  tokens the target itself would have produced, and the first mismatch is
  replaced by the target's own argmax. Acceptance only changes HOW MANY
  target dispatches the sequence costs, never the tokens.
- **Static shapes**: every round runs ONE target `extend()` over a fixed
  (gamma+1)-token span — a single compiled program per attend bucket —
  plus gamma-1 draft single steps and a 1-or-2-token draft catch-up
  span. No data-dependent shapes; acceptance is host-side control flow
  between dispatches, exactly like the pipeline's other host drivers.
- **Batch-safe**: drafts are per-row; a round accepts the MINIMUM
  accepted prefix across rows. Rows that matched deeper simply re-derive
  those tokens next round — greedy is deterministic, so exactness is
  unaffected (this trades a little wasted compute for scalar `pos`
  bookkeeping and static shapes, the TPU-friendly end of the trade).
- **Cache discipline**: rejected proposals leave K/V rows beyond the
  committed position; every such row is overwritten by the next round's
  span write before any query can attend it (the span mask keeps
  k_pos <= q_pos), so rollback is free — the committed position IS the
  rollback state.

The draft can be any pipeline over the same vocabulary (typically a much
smaller model). Speedup = (accepted+1 tokens per verify) vs (1 token per
target step); acceptance depends on draft/target agreement, so the
measured `acceptance_rate` is reported alongside tokens.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .decode import DecodePipeline, validate_capacity

__all__ = ["SpeculativeDecoder"]


def _device_rounds_eligible(pipe: DecodePipeline) -> Optional[str]:
    """None if `pipe`'s stage programs can be inlined into ONE jitted
    round program, else the reason they cannot: explicit per-stage device
    placement inserts host-driven transfers between stages (a single XLA
    program is single-(mesh-)device), and tp / tp x ep meshes place
    params+caches with shardings the fused program would have to
    re-specify."""
    if any(st["device"] is not None for st in pipe.stages):
        return "per-stage device placement"
    if pipe.mesh is not None:
        return "tensor-parallel mesh"
    if pipe.ep_mesh is not None:
        return "expert-parallel mesh"
    if pipe.tp_ep_mesh is not None:
        return "tp x ep mesh"
    return None


class SpeculativeDecoder:
    """Greedy speculative decoding over two `DecodePipeline`s.

    `gamma` is the draft lookahead per round: the draft proposes gamma
    tokens, one target `extend()` scores all of them plus a bonus
    position. gamma is fixed for the whole generation so the verify span
    compiles once per attend bucket.

    `sync` picks how many host round trips a round costs:

    - ``"host"``: every draft argmax reads back to the host — g+1
      device round trips per round, each a fixed dispatch + readback
      cost that can eat the verify-span win.
    - ``"device"``: the DRAFT side of the round — catch-up span plus
      gamma-1 draft steps, argmax feeding argmax on device — is one
      compiled program returning one packed [B, gamma] proposal array
      (ONE readback); the target verify then runs through the SAME
      compiled stage programs the host mode uses (one more readback for
      its argmax row). TWO syncs per round vs g+1. Token-identical to
      "host": committed tokens are always the target program's own
      greedy continuations (the standard speculative exactness
      argument), and the target program is literally the same compiled
      object in both modes. (A fully-fused round — verify + acceptance
      in the same program, ONE sync — was built and measured on chip:
      inlining the target stages changes XLA fusion, and at bf16 the
      fused verify's argmax flips on near-ties, 16% token divergence on
      random-init logits. Reverted to the draft-only fusion, which is
      numerics-robust by construction; docs/DECODE.md records the
      negative.)
    - ``"auto"`` (default): "device" when the draft pipeline's stage
      programs can legally inline into one jitted program (no per-stage
      device placement, no tp/ep/tp x ep mesh —
      `_device_rounds_eligible`), else "host".

    `last_sync_count` records the host round trips of the latest
    generate() (the chip A/B's measured quantity: docs/DECODE.md).
    """

    def __init__(self, target: DecodePipeline, draft: DecodePipeline,
                 gamma: int = 4, sync: str = "auto",
                 target_kv=None, draft_pool=None):
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        if (target_kv is None) != (draft_pool is None):
            raise ValueError(
                "paged speculative decoding needs BOTH pools: target_kv "
                "(the decode plane's PagedKvBackend) and draft_pool (a "
                "KvPagePool over the draft pipeline)")
        for name, pipe in (("target", target), ("draft", draft)):
            whole = [leaf for leaf, spec in (pipe.cache_leaves or {}).items()
                     if getattr(spec, "whole", False)]
            if whole:
                # a round writes gamma rows past the committed position and
                # a rejection steps back over them: rows a position are
                # simply overwritten, a state a request has moved on
                raise NotImplementedError(
                    f"the {pipe.family.name} family ({name}) keeps {whole} "
                    "a request, not a position: a rejected draft would need "
                    "the state of an earlier position, and speculative "
                    "verify has no snapshot to roll back to")
            rings = [leaf for leaf, spec in (pipe.cache_leaves or {}).items()
                     if getattr(spec, "length", 0)]
            if rings:
                # the gamma rows of a round take the slots of the oldest
                # positions in the window, which a rejection needs again
                raise NotImplementedError(
                    f"the {pipe.family.name} family ({name}) keeps {rings} "
                    "as rings: a rejected draft's rows have overwritten "
                    "positions the window still holds, and speculative "
                    "verify has no snapshot to roll back to")
        if target.cfg.vocab_size != draft.cfg.vocab_size:
            raise ValueError(
                "draft and target must share a vocabulary: "
                f"{draft.cfg.vocab_size} vs {target.cfg.vocab_size}")
        for name, pipe in (("target", target), ("draft", draft)):
            cfg = pipe.cfg
            if cfg.n_experts and cfg.capacity_factor < cfg.n_experts:
                # capacity routing is not per-token: a verify span routes
                # its tokens jointly, which serial decode steps cannot
                # reproduce — the greedy-exact guarantee would not hold
                raise ValueError(
                    f"capacity-bounded MoE {name} breaks the greedy-exact "
                    "guarantee (span routing != per-step routing); use a "
                    "dropless config (capacity_factor >= n_experts)")
        if sync not in ("auto", "host", "device"):
            raise ValueError(f"sync must be auto/host/device, got {sync!r}")
        # only the DRAFT is fused into one program; the target verify
        # rides its normal stage programs in both modes
        blockers = {name: why for name, pipe in (("draft", draft),)
                    if (why := _device_rounds_eligible(pipe)) is not None}
        if sync == "device" and blockers:
            raise ValueError(
                f"sync='device' unavailable: {blockers} (the draft round "
                "must compile into one program); use sync='auto' or "
                "'host'")
        self.target = target
        self.draft = draft
        self.gamma = gamma
        self.sync = "host" if sync == "auto" and blockers else \
            ("device" if sync == "auto" else sync)
        # paged mode (docs/SERVING.md): draft/verify caches live as
        # page-shaped views over KvPagePools instead of dense max_len
        # slots — speculation's cache residency is charged against the
        # SAME capacity plane as the decode executor's requests (and a
        # separate draft-layout pool), so admission tokens, brownout
        # eviction pressure and the orphan sweep all see it
        self.kv = target_kv
        self.draft_pool = draft_pool
        self._live: set = set()   # owners mid-generate (sweep liveness)
        import itertools
        self._seq = itertools.count()
        self.last_acceptance_rate: Optional[float] = None
        self.last_sync_count: Optional[int] = None
        self._round_cache: dict = {}

    def _draft_round_fn(self, batch: int, catch_len: int, d_read):
        """The compiled device-side DRAFT round (sync='device'): catch-up
        span + gamma-1 proposal steps with argmax feeding argmax on
        device, returning one packed [B, gamma] proposal array. Cached
        per (batch, catch span length, attend bucket) — a handful of
        variants per generation, the same compile-per-discrete-value
        pattern as the attend buckets themselves."""
        key = (batch, catch_len, d_read)
        fn = self._round_cache.get(key)
        if fn is not None:
            return fn
        g = self.gamma
        draft_fns = [st["decode"] for st in self.draft.stages]

        def run_stages(params_list, data, caches, pos):
            out = []
            for fn, p, c in zip(draft_fns, params_list, caches):
                if d_read is None:
                    data, c = fn(p, data, c, pos)
                else:
                    data, c = fn(p, data, c, pos, read_len=d_read)
                out.append(c)
            return data, out

        def greedy(logits):     # [B, V] -> [B] int32, the host rule
            return jnp.argmax(logits.astype(jnp.float32), -1) \
                .astype(jnp.int32)

        # params enter as ARGUMENTS, never closures: a closed-over param
        # pytree would bake the full model weights into the program as
        # constants — the serialized HLO then carries them to the
        # compiler (hundreds of MB)
        @jax.jit
        def draft_round(d_params, d_caches, catch, d_pos):
            # catch-up span over committed-but-unseen tokens ...
            x, d_caches = run_stages(d_params, catch, d_caches, d_pos)
            props = [greedy(x[:, -1])]
            # ... then gamma-1 proposals, argmax feeding argmax ON DEVICE
            for k in range(g - 1):
                x, d_caches = run_stages(d_params, props[-1][:, None],
                                         d_caches,
                                         d_pos + catch_len + k)
                props.append(greedy(x[:, -1]))
            return jnp.stack(props, axis=1), d_caches      # [B, g]

        self._round_cache[key] = draft_round
        return draft_round

    def precompute_prefix(self, prefix_ids) -> dict:
        """Prompt caching for speculative decoding: prefill the shared
        prefix through BOTH pipelines (each model needs its own K/V) and
        return one handle for `generate(..., prefix=)`."""
        return {"target": self.target.precompute_prefix(prefix_ids),
                "draft": self.draft.precompute_prefix(prefix_ids)}

    # -- paged caches (kv/pool.py) ----------------------------------------

    def attach_paged(self, target_kv, draft_pool) -> None:
        """Arm paged mode after construction. The serving layer builds
        the decoder BEFORE the decode plane's PagedKvBackend exists
        (tools/serve.py constructs the backend inside `_Service`), so
        the pools are attached here rather than via `__init__`."""
        if target_kv is None or draft_pool is None:
            raise ValueError("attach_paged needs BOTH target_kv and "
                             "draft_pool (see __init__)")
        self.kv = target_kv
        self.draft_pool = draft_pool

    def live_rids(self) -> set:
        """Owners currently mid-generate. The serving governor unions
        this into the pool sweeps' live set, so a speculative request's
        pages are never taken for orphans while its thread runs."""
        return set(self._live)

    def sweep_orphans(self) -> int:
        """Reclaim DRAFT-pool pages whose generation died between page
        charge and release (the target pool's pages ride the decode
        plane's sweep — tools/serve.py passes `live_rids` into it)."""
        if self.draft_pool is None:
            return 0
        return self.draft_pool.sweep_leaked(lambda: self.live_rids())

    def _alloc_paged(self, owner, batch: int, prompt_len: int,
                     new_tokens: int):
        """Charge pages for one paged generation — target pages from the
        decode plane's pool (speculation competes for the SAME capacity
        as executor requests), draft pages from the draft-layout pool —
        and return the gathered page-shaped working caches. The views
        are `[L, B, pages * page_size, ...]`, shorter than dense
        `max_len` slots: positions past the window are masked to exact
        softmax zeros, so tokens are identical to the dense path
        (kv/backend.py's numerics argument; tests pin it). Speculative
        caches are never shared cross-request, so the pages are held as
        the capacity reservation and the rounds run on the views —
        scatters back to the arena would be dead stores."""
        from ..kv.pool import pages_for
        g = self.gamma
        t_per = self.kv.pages_needed(prompt_len, new_tokens + g)
        dpool = self.draft_pool
        # the draft pool buckets like PagedKvBackend.pages_needed: page
        # spans round up to a power of two so the draft programs compile
        # per bucket, not per exact prompt length
        d_per = pages_for(prompt_len + new_tokens + g, dpool.page_size)
        cap = pages_for(self.draft.max_len, dpool.page_size)
        p2 = 1
        while p2 < d_per:
            p2 *= 2
        d_per = min(p2, cap)
        t_rows: list = []
        d_rows: list = []
        try:
            for _ in range(batch):
                t_rows.append(self.kv.pool.alloc(t_per))
            for _ in range(batch):
                d_rows.append(dpool.alloc(d_per))
        except BaseException:
            for row in t_rows:
                self.kv.pool.release(row)
            for row in d_rows:
                dpool.release(row)
            raise
        # ledger adoption: a thread that dies past this point is
        # reclaimable by the orphan sweeps (owner is in _live already,
        # so a concurrent sweep cannot take the pages for dead)
        self.kv.pool.adopt(owner, [p for row in t_rows for p in row])
        dpool.adopt(owner, [p for row in d_rows for p in row])
        t_table = np.asarray(t_rows, np.int32)
        d_table = np.asarray(d_rows, np.int32)
        with self.kv._arena_lock:
            t_caches = [self.kv.pool.gather(i, t_table)
                        for i in range(len(self.target.stages))]
        d_caches = [dpool.gather(i, d_table)
                    for i in range(len(self.draft.stages))]
        return t_caches, d_caches

    def _release_paged(self, owner) -> None:
        """Drop both pools' page references (claim-then-release through
        the owner ledgers, so the release path and the orphan sweeps
        race benignly) and delist the owner."""
        pids = self.kv.pool.disown(owner)
        if pids is not None:
            self.kv.pool.release(pids)
        pids = self.draft_pool.disown(owner)
        if pids is not None:
            self.draft_pool.release(pids)
        self._live.discard(owner)

    def generate(self, ids, new_tokens: int, prefix: Optional[dict] = None,
                 rid=None):
        """Greedy-decode `new_tokens` continuations of prompt `ids`
        [B, S]; returns [B, S + new_tokens] (prompt included), token-
        identical to `target.generate(ids, new_tokens)` for fp caches.
        Sets `last_acceptance_rate` (accepted drafts / proposed drafts).

        `prefix` (from this decoder's `precompute_prefix`) seeds both
        pipelines with a shared prompt prefix; `ids` is then each
        request's SUFFIX (non-empty), and the returned array omits the
        prefix — matching `DecodePipeline.generate`'s prefix contract.

        In paged mode (`target_kv`/`draft_pool` set) the caches are
        page-shaped views over the pools instead of dense slots —
        token-identical — and `rid` names the page owner in the pools'
        ledgers (defaults to a fresh unique id)."""
        ids = jnp.asarray(ids, jnp.int32)
        batch, suffix_len = ids.shape
        base = prefix["target"]["len"] if prefix else 0
        prompt_len = suffix_len + base
        if prefix is not None:
            # each sub-handle must match ITS pipeline's cache layout
            # (round-4 advice: reject foreign handles before jit)
            self.target.check_prefix(prefix["target"])
            self.draft.check_prefix(prefix["draft"])
            if prefix["draft"]["len"] != base:
                raise ValueError("target/draft prefix lengths differ: "
                                 f"{base} vs {prefix['draft']['len']}")
            if suffix_len == 0:
                raise ValueError("prefix reuse needs a non-empty suffix")
        if new_tokens <= 0:
            return ids
        if self.kv is not None and prefix is not None:
            raise ValueError(
                "paged speculative decoding replaces dense prefix "
                "handles (the serving layer expands prefixes into "
                "prompt tokens); submit the full prompt instead")
        g = self.gamma
        # worst case writes a full span past the last emitted token
        validate_capacity(self.target.cfg, self.target.max_len,
                          prompt_len, new_tokens + g)
        validate_capacity(self.draft.cfg, self.draft.max_len,
                          prompt_len, new_tokens + g)

        owner = None
        try:
            if self.kv is not None:
                owner = str(rid) if rid is not None \
                    else f"spec{next(self._seq)}"
                self._live.add(owner)
                t_caches, d_caches = self._alloc_paged(
                    owner, batch, prompt_len, new_tokens)
                # the prompt pass runs as a span at offset 0 over the
                # page-shaped views — token-identical to _prefill (the
                # same masking rule chunked prefill relies on)
                t_out, t_caches = self.target.extend(ids, t_caches, 0)
                _, d_caches = self.draft.extend(ids, d_caches, 0)
                known = []
            elif prefix is None:
                t_out, t_caches = self.target._prefill(ids)
                _, d_caches = self.draft._prefill(ids)
                # the draft has seen the whole prompt; catch-up tokens
                # are all emitted ones
                known = []
            else:
                from .decode import _repeat_batch
                t_caches = [_repeat_batch(c, batch)
                            for c in prefix["target"]["caches"]]
                t_out, t_caches = self.target.extend(ids, t_caches, base)
                d_caches = [_repeat_batch(c, batch)
                            for c in prefix["draft"]["caches"]]
                # the draft has seen only the prefix: its first catch-up
                # span covers the whole suffix too (one transfer, [B]
                # rows)
                known = list(np.asarray(ids, np.int32).T)
            return self._rounds(ids, new_tokens, t_out, t_caches,
                                d_caches, known, base, prompt_len,
                                bool(prefix))
        finally:
            if owner is not None:
                self._release_paged(owner)

    def _rounds(self, ids, new_tokens: int, t_out, t_caches, d_caches,
                known: list, base: int, prompt_len: int,
                prefixed: bool):
        """The draft-propose / target-verify loop (seeding done): shared
        verbatim by the dense, prefix-seeded and paged cache paths."""
        g = self.gamma
        batch = ids.shape[0]
        pending = np.asarray(
            jnp.argmax(t_out[:, -1].astype(jnp.float32), -1),
            np.int32)                       # [B] first continuation token
        syncs = 1                           # the first-token readback
        n_suffix = len(known)    # known = suffix tokens ++ emissions,
        known.append(pending)    # sitting at positions [d_floor, ...)
        d_floor = base if prefixed else prompt_len
        n_emitted = 1
        t_pos = prompt_len   # target cache rows [0, t_pos) are committed
        d_pos = d_floor      # draft cache rows [0, d_pos) are committed
        proposed = accepted = 0
        device_rounds = self.sync == "device"

        while n_emitted < new_tokens:
            # --- draft: catch up on committed tokens it hasn't seen
            # (suffix+pending on the first prefix-seeded round; then 1
            # token normally, 2 after a fully-accepted round), then
            # propose gamma tokens autoregressively
            catch = np.stack(known[d_pos - d_floor:], axis=1)
            if device_rounds:
                # the draft side in ONE program, one packed readback:
                # the attend bucket for the round's deepest draft
                # position is chosen host-side (positions are host
                # bookkeeping, never read back) and bound statically;
                # earlier in-round steps attending through the wider
                # bucket is numerically identical (extra positions are
                # masked). The target verify below uses the SAME
                # compiled stage programs as sync='host', so tokens
                # cannot diverge between modes.
                c_len = catch.shape[1]
                draft_round = self._draft_round_fn(
                    batch, c_len,
                    self.draft._read_len(d_pos, c_len + g - 1))
                props_arr, d_caches = draft_round(
                    [st["params"] for st in self.draft.stages],
                    d_caches, jnp.asarray(catch), d_pos)
                props_arr = np.asarray(props_arr, np.int32)    # sync 1
                syncs += 1
                props = [props_arr[:, k] for k in range(g)]
                # (d_pos is reconciled from `a` at the end of the loop)
            else:
                d_logits, d_caches = self.draft.extend(catch, d_caches,
                                                       d_pos)
                d_pos += catch.shape[1]
                props = [np.asarray(
                    jnp.argmax(d_logits[:, -1].astype(jnp.float32), -1),
                    np.int32)]
                syncs += 1
                for _ in range(g - 1):
                    d_logits, d_caches = self.draft.extend(
                        props[-1][:, None], d_caches, d_pos)
                    props.append(np.asarray(
                        jnp.argmax(d_logits[:, -1].astype(jnp.float32), -1),
                        np.int32))
                    syncs += 1
                    d_pos += 1

            # --- target: one span forward scores pending + proposals —
            # THE SAME compiled stage programs in both sync modes, the
            # token-identity anchor
            span = np.stack([pending] + props, axis=1)        # [B, g+1]
            t_logits, t_caches = self.target.extend(span, t_caches,
                                                    t_pos)
            targets = np.asarray(
                jnp.argmax(t_logits.astype(jnp.float32), -1), np.int32)
            syncs += 1

            # --- accept the minimum matching prefix across rows
            a = 0
            while a < g and bool(np.all(props[a] == targets[:, a])):
                a += 1
            proposed += g
            accepted += a
            known.extend(props[:a] + [targets[:, a]])  # drafts + correction
            n_emitted += a + 1
            pending = targets[:, a]
            t_pos += a + 1
            # draft rows hold [pending, p1..p_{g-1}] from this round's
            # catch-up+proposals; committed among them: pending..p_a
            d_pos = t_pos - 1 if a == g else t_pos

        self.last_acceptance_rate = accepted / proposed if proposed else None
        self.last_sync_count = syncs
        gen = jnp.asarray(np.stack(known[n_suffix:n_suffix + new_tokens],
                                   axis=1))
        return jnp.concatenate([ids, gen], axis=1)
