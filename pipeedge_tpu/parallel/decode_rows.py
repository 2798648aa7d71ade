"""A decode step for rows that stand each at its own position: the stage
program the served executor (`parallel/batcher.py`) steps every running
request's row with, beside `decode.make_stage_fns`' scalar-`pos` programs,
which `DecodePipeline.generate` and every other driver keep as they are.

- **A stage-wide cache.** `init_cache(cfg, n_blocks, batch=slots, ...)`,
  made once a stage: slot r is one row of one running request. A request's
  prompt pass runs alone at its own length on a cache of its own (the
  scalar programs), and `install_rows` then copies its rows into its slots.
- **A position a row.** `rows_step(params, ids, hidden, cache, where)`,
  `where` the first slot `base` and then `pos [R]`: row r of slots [base,
  base + R) is embedded, rotated, written and masked at `pos[r]`; a slot
  with `pos[r] < 0` is dead, computed and discarded (it writes nothing and
  its token is not taken). R is a rung of `row_rungs`,
  static; where the rung lies in the cache and the attended window are
  not: `base` is traced, and the step walks the cache in blocks up to the
  furthest live row (`stage_cache.attend_rows`), so a rung is ONE program
  whatever the lengths and whichever slots the live rows hold (a request
  left alone in slot 5 steps at the rung of one row).
- **The pick inside.** The rows that step together are greedy, so the last
  stage ends with the argmax and hands back the stage-wide `ids [slots, 1]`
  with the live rows' next tokens put in: the next step's input, on the
  device, with no dispatch between.

Which families: those whose block has a row step. The plain dense block
(GPT-2's, `decode._block_step`) has `block_step_rows` below; a family with
its own `cached_block_step` says so with `FamilySpec.rows_block_step`:
llama's, and the window-and-full block of laguna and mellum
(`models/laguna.py`), the first whose stage is runs of more than one kind of
block, whose cache has rings beside rows a position (a ring a slot, each at
its own row's position: `stage_cache.attend_rows`, `write_rows_at`), whose
FFN is routed experts (the router drops nothing, so rows do not compete; a
dead slot's row goes to no expert) and whose block steps count into a
`stats` leaf. The step runs the stage's blocks as every stage program does
(`decode._run_blocks`: a scan a run, each run at its own leaves' layers).

What still steps one dispatch a request, and why (`rows_block_fn` answers
None):
- a leaf that is a row a request (`whole`: a recurrent state or a
  convolution's tail, replaced by every call): a dead slot's row would have
  to leave its state as it was, and a joining request's state is no row to
  write at a position; nothing here installs or masks one (qwen3_next,
  lfm2, minicpm_sala's lightning layers, nemotron_h, granite_hybrid,
  brumby);
- a strided leaf (minicpm_sala's pooled keys, a row every few positions
  written by the call that completes it): rows at different positions
  complete different rows;
- a family whose block has no row step (keye's selection, kimi's latent
  attention);
- GPT-2's capacity-bound experts (`cfg.n_experts` without a top-k: rows
  compete for an expert's capacity, so a row's result would depend on its
  neighbours);
- an int8 cache (the walk reads a block as stored, and a quantized block
  has scales beside it) and the sharded makers (tp, ep, tp x ep, sp: their
  programs run under `shard_map` with one `pos`).
"""
from __future__ import annotations

import heapq
from functools import lru_cache, partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..models import ShardConfig
from ..models.layers import TransformerConfig, layer_norm
from ..models.stage_cache import (STATS, LayerCache, RowsAt, attend_rows,
                                  merge_stats, ring_names, stride_names,
                                  whole_names, write_rows_at)
from .decode import (_block_tail, _qkv, _run_blocks, run_geometry,
                     stage_blocks)

# positions a block of the walk holds. A turn of the walk costs about 19 us
# whatever it reads (gpt2-medium on the v5e, my chip run, PR 55: 48 rows at
# 1,000 positions 12.3 ms in blocks of 128, 10.3 of 256, 9.3 of 512, 8.5 with
# the scalar step's one window of 1,024), and a longer block reads past the
# furthest row by more: at 600 positions 8.47, 8.28 and 9.34 ms, at 100
# 3.27, 4.07 and 5.68; 8 and 16 rows read the same way
WALK_BLOCK = 256


def block_step_rows(p: Dict, x: jax.Array, bcache: LayerCache, at: RowsAt,
                    cfg: TransformerConfig, block: int):
    """`decode._block_step` for one token a row at `at.pos[r]`: the same
    projections and tail around `attend_rows`."""
    normed = layer_norm(p["ln_before"], x, cfg.layer_norm_eps)
    q, k_new, v_new = _qkv(p, normed, cfg)
    ctx, bcache = attend_rows(bcache, q, k_new, v_new, at, block, cfg)
    return _block_tail(p, x, ctx, cfg), bcache


def embed_rows(pe: Dict, tok: jax.Array, pos) -> jax.Array:
    """`decode.single_token_embed` at a position a row: [R, 1] -> [R, 1, D]."""
    return (jnp.take(pe["wte"], tok.reshape(-1), axis=0)
            + jnp.take(pe["wpe"], pos, axis=0))[:, None]


def rows_block_fn(pipe):
    """The block step that takes row positions for `pipe`'s stages, or None
    where its programs take one `pos`. What a call's family and leaves say
    decides (the module's docstring has the reasons): None for an int8
    cache and the sharded makers; the plain dense block's `block_step_rows`
    where the family has no `cached_block_step` (None where its experts are
    capacity-bound); else the family's `rows_block_step`, if it has one and
    every leaf it names is a row a position or a ring (no `whole` leaf, no
    strided one)."""
    if (pipe.cache_bits or pipe.mesh is not None or pipe.ep_mesh is not None
            or pipe.tp_ep_mesh is not None or pipe.sp_degree != 1):
        return None
    if getattr(pipe.family, "cached_block_step", None) is None:
        return None if pipe.cfg.n_experts else block_step_rows
    if whole_names(pipe.cache_leaves) or stride_names(pipe.cache_leaves):
        return None
    return getattr(pipe.family, "rows_block_step", None)


def walk_block(max_len: int, rows: int) -> int:
    """Positions a block of the walk holds at `rows` rows: `WALK_BLOCK`, and
    the whole row where one row steps alone. One row's window is 0.1 of its
    step's bytes beside the weights, and the chip's compiler answers a
    shorter slice of ONE row with a copy of the whole stack into a layout of
    its own (9 ms a step where 1.4 are due: my chip run, PR 55;
    `tests/test_chip_compile.py` holds every rung to no such copy)."""
    return max_len if rows == 1 else min(WALK_BLOCK, max_len)


def row_rungs(slots: int) -> tuple:
    """The counts of rows a step is compiled for: 1 (a lone request keeps a
    one-row step), 8 and its multiples by four, and every slot. A step takes
    the least rung that spans its live slots, lowest to highest. A rung is one
    program, traced and lowered before traffic comes (`StageRows.warm`):
    0.42 s of every set-up each, a compile more in a first run (my chip
    run, PR 55), which is why the ladder is no finer."""
    rungs, rung = {1, slots}, 8
    while rung < slots:
        rungs.add(rung)
        rung *= 4
    return tuple(sorted(rungs))


@lru_cache(maxsize=None)     # one program a (model, stage, rung), not an executor
def make_rows_step(family, cfg: TransformerConfig,
                   shard_config: ShardConfig, block_fn, rows: int,
                   block: int):
    """The jitted `rows_step(params, ids, hidden, cache, where) -> (out,
    cache)` of one stage at `rows` rows: `ids [slots, 1]` the stage-wide
    next tokens (read by the first stage, updated by the last), `hidden
    [rows, 1, D]` the stage before's output (None at the first), `where
    [1 + rows]` int32 the first of the rows' slots and then each row's
    position, negative where the slot is dead (one array: every argument
    from the host is a transfer of its own, 0.1 ms of a dispatch on the
    chip: my chip run, PR 55). `out` is the hidden state, or at the last
    stage `ids` with each live row's greedy pick in its slot. The cache is
    DONATED, as in every stage program."""
    embed = getattr(family, "decode_embed", None) or embed_rows
    geometry = run_geometry(family, cfg, shard_config)
    rings = ring_names(geometry["leaves"])

    def step_block(bp, y, bcache, at, cfg_, prefill):
        return block_fn(bp, y, bcache, at, cfg_, block)

    def rows_step(params, ids, hidden, cache, where):
        base, pos = where[0], where[1:]
        at = jnp.maximum(pos, 0)
        at = RowsAt(base, at, jnp.max(at), pos >= 0)
        mine = jax.lax.dynamic_slice(ids, (base, 0), (rows, 1))
        x = embed(params["embeddings"], mine, at.pos) \
            if shard_config.is_first else hidden
        # the stage's runs of like blocks as every stage program scans
        # them, each block handed `at` where those are handed one `pos`
        x, cache = _run_blocks(
            stage_blocks(params), x, cache, at, cfg, False,
            block_fn=step_block, **geometry,
            write=lambda held, new: write_rows_at(held, new, base, pos,
                                                  rings))
        if not shard_config.is_last:
            return x, cache
        logits = family.finalize(params["final"], x, cfg)
        token = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        picked = jnp.where(pos >= 0, token.astype(ids.dtype), mine[:, 0])
        return jax.lax.dynamic_update_slice(ids, picked[:, None],
                                            (base, 0)), cache

    return jax.jit(rows_step, donate_argnums=(3,))


@partial(jax.jit, donate_argnums=(0,))
def install_rows(stage_cache, cache, slots):
    """A request's rows, as its prompt pass left them (`cache`, leaves
    `[L, B, T, ...]`, a ring's `[L, B, W, ...]`), into slots `slots [B]` of
    the stage-wide cache (DONATED): one in-place update a leaf a row, whole
    rows and whole rings, so nothing a slot's last owner wrote outlives it.
    The `stats` leaf is no row: what the prompt pass counted is added up
    apart (`StageRows.install`)."""
    for name, rows in cache.items():
        if name == STATS:
            continue
        buf = stage_cache[name]
        for b in range(rows.shape[1]):
            buf = jax.lax.dynamic_update_slice(
                buf, rows[:, b:b + 1].astype(buf.dtype),
                (0, slots[b]) + (0,) * (buf.ndim - 2))
        stage_cache = dict(stage_cache, **{name: buf})
    return stage_cache


_merge_stats = jax.jit(merge_stats)


@jax.jit
def join_ids(ids, step_ids, slots):
    """A joining request's first tokens `step_ids [B, 1]` (its prompt
    pass's pick) into its slots of the stage-wide `ids [slots, 1]`."""
    return ids.at[slots].set(step_ids.astype(ids.dtype))


class StageRows:
    """The stage-wide state of the rows that step together: a cache of
    `slots` rows a stage, the next tokens `ids [slots, 1]` on the device,
    the table of free slots (lowest first, so the live rows stay close and
    a step takes the least rung that spans them), and the step
    programs, one a stage a rung. The executor owns one; nothing here
    locks."""

    def __init__(self, pipe, slots: int, block_fn):
        self.pipe, self.slots = pipe, int(slots)
        self.rungs = row_rungs(self.slots)
        self.caches = pipe._fresh_caches(self.slots)
        # what the installed requests' prompt passes counted, a stage
        # (`stats` leaves, where the family has them): the steps' counts
        # are in the stage-wide caches' own
        self.prompt_stats = [jnp.zeros_like(cache[STATS])
                             for cache in self.caches if STATS in cache]
        self.ids = jnp.zeros((self.slots, 1), jnp.int32)
        self._free = list(range(self.slots))        # a heap
        total = 4 * pipe.cfg.num_hidden_layers
        self._steps, first = {}, 1
        for i, st in enumerate(pipe.stages):
            last = first + 4 * st["n_blocks"] - 1
            sc = ShardConfig(first, last, is_first=first == 1,
                             is_last=last == total)
            # a stage's `wrap`, where one is set, wraps each of its programs
            # (tools/serve.py --inject-stall)
            wrap = st.get("wrap") or (lambda program: program)
            for rung in self.rungs:
                self._steps[i, rung] = wrap(make_rows_step(
                    pipe.family, pipe.cfg, sc, block_fn, rung,
                    walk_block(pipe.max_len, rung)))
            first = last + 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    def take(self, n: int) -> List[int]:
        """The `n` lowest free slots."""
        return [heapq.heappop(self._free) for _ in range(n)]

    def free(self, slots) -> None:
        for slot in slots:
            heapq.heappush(self._free, slot)

    def walked(self, rung: int, reach: int) -> int:
        """Positions a row of a `rung`-row step reads with its furthest
        live row at `reach`: whole blocks of the walk."""
        block = walk_block(self.pipe.max_len, rung)
        return min(-(-max(reach, 0) // block) * block, self.pipe.max_len)

    def span(self, low: int, top: int):
        """The least rung whose rows span slots `low` to `top`, and where it
        starts: at `low`, or as far below it as the cache's end asks."""
        rung = next(r for r in self.rungs if r > top - low)
        return rung, min(low, self.slots - rung)

    def _put(self, i: int, x):
        device = self.pipe.stages[i]["device"]
        return x if device is None or x is None else jax.device_put(x, device)

    def install(self, i: int, cache, slots) -> None:
        """A request's rows of stage `i`, after its prompt pass, into its
        slots, and what that pass counted onto the stage's prompt counts."""
        self.caches[i] = install_rows(self.caches[i], cache,
                                      np.asarray(slots, np.int32))
        if STATS in cache:
            self.prompt_stats[i] = _merge_stats(self.prompt_stats[i],
                                                cache[STATS])

    def join(self, step_ids, slots) -> None:
        """A request's first tokens into its slots of `ids`."""
        self.ids = join_ids(self.ids, step_ids, np.asarray(slots, np.int32))

    def step(self, i: int, hidden, base: int, pos):
        """Dispatch stage `i`'s step of the `len(pos)` rows from slot `base`
        on, row r at `pos[r]` (on the device); the last stage's output is
        the new `ids`."""
        st = self.pipe.stages[i]
        where = np.concatenate(([base], pos), dtype=np.int32)
        out, self.caches[i] = self._steps[i, len(pos)](
            st["params"], self._put(i, self.ids), self._put(i, hidden),
            self.caches[i], self._put(i, where))
        if i == len(self.pipe.stages) - 1:
            self.ids = out
        return out

    def warm(self) -> None:
        """Build every rung's programs before the first request: a step of
        dead rows writes nothing and takes no token."""
        for rung in self.rungs:
            pos, hidden = np.full(rung, -1, np.int32), None
            for i in range(len(self.pipe.stages)):
                hidden = self.step(i, hidden, 0, pos)
        jax.block_until_ready(self.ids)
