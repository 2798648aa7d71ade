"""Autoregressive KV-cache decoding through the pipeline (the GPT-2 family
by default; llama, keye and kimi through `FamilySpec.cached_block_step`).

NEW capability beyond the reference (whose model list is encoder-only and
whose runtime is single-shot batch inference). TPU-first design:

- **Static shapes everywhere**: the KV cache is a fixed [n_blocks, B,
  max_len, H*Dh] buffer per stage; the current length rides as a traced
  scalar `pos`, future positions are masked. One compiled prefill program +
  one compiled decode-step program per stage serve the whole generation —
  no per-step recompilation (the reference's dynamic-shape wire protocol
  has no answer to this; SURVEY.md §7 'hard parts').
- **Block-aligned pipeline stages**: each stage holds its blocks' cache,
  consumes the previous stage's hidden state for the current token, and
  returns its own — the same stage-edge discipline as the forward
  pipeline (quantizable, device-placeable). Autoregression serializes
  decode steps, so parallelism comes from the batch dimension; stages
  still split the model across devices for memory capacity.
- Attention over the cache streams the window as it is stored, one masked
  matmul a product — MXU-shaped, no gather, no copy of the window.

Greedy decoding matches HF `GPT2LMHeadModel.generate(do_sample=False)`
token-for-token (tests/test_decode.py).
"""
from __future__ import annotations

import math
from collections import deque
from functools import cached_property, partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry
from ..telemetry import generate_account
from ..telemetry import metrics as prom
from ..utils import jax_compat

from ..models import ShardConfig, plan_shard
from ..models.stage_cache import (STATS, Cache, LayerCache, LayerSlice,
                                  attend, attend_width,
                                  cache_update_and_read,
                                  cache_write_quantized, init_cache,
                                  leaf_owners, read_stats, ring_names,
                                  shares_layers, stride_names, whole_names,
                                  write_rows)
from ..models.shard import BlockRuns, kind_runs
from ..models.layers import (TransformerConfig, dense, gelu_new, layer_norm)

# A stage's cache is updated IN PLACE. Every jitted stage program (prefill
# and decode step of the plain, tp, ep, tp x ep and sp makers) DONATES its
# cache argument: the buffers handed in are dead after the call, and the
# returned cache lives in the same memory. A caller rebinds
# (`out, cache = step(params, data, cache, pos)`) and never reads the old
# reference again; whoever must keep a cache across a call (a prefix
# handle, a beam reshuffle) hands the program a copy (`_repeat_batch`,
# `_gather_batch`). What a block step sees of it: models/stage_cache.py.


def _qkv(p: Dict, normed: jax.Array, cfg: TransformerConfig):
    b, s, _ = normed.shape
    h, hd = cfg.num_attention_heads, cfg.head_dim
    return (dense(p["q"], normed).reshape(b, s, h, hd),
            dense(p["k"], normed).reshape(b, s, h, hd),
            dense(p["v"], normed).reshape(b, s, h, hd))


# per-tensor int8 window bytes the kernel may stage in VMEM: the window
# is loaded whole per batch cell (grid is (batch,)), so huge unbucketed
# windows must stay on the XLA path instead of dying in Mosaic lowering.
# 1 MB = the measured-good regime (width 1024 at 16x64 heads ran on
# chip; width 4096 hit a 36 MB scoped-vmem stack vs the 16 MB limit)
_INT8_KERNEL_VMEM_CAP = 1 << 20


def _int8_kernel_env() -> int:
    """Resolve the PIPEEDGE_INT8_DECODE_ATTEND opt-in (empty/0/false/no/off
    all mean off; '2' forces the batch-as-sublane kernel variant, 'auto'
    applies the measured routing policy — kernel v2 only for attend
    windows <= 256 where it beat XLA in three separate chip sessions,
    XLA everywhere else (docs/DECODE.md) — and any other truthy value
    forces variant 1). Callers resolve this ONCE at pipeline
    construction and bind the answer into the stage programs — compiled
    decode steps are cached per shape/read_len, so a trace-time env read
    would silently ignore later toggles for already-compiled shapes
    (round-4 advice)."""
    import os
    env = (os.getenv("PIPEEDGE_INT8_DECODE_ATTEND") or "").strip().lower()
    if not env or env in ("0", "false", "no", "off"):
        return 0
    if env == "auto":
        return 3
    return 2 if env == "2" else 1


def _resolve_int8_optin(override=None) -> int:
    """Construction-time resolution of the int8 decode-attend routing
    (the promotion seam, ISSUE 19): an explicit override — constructor
    arg `int8_decode_attend` / `--int8-decode-attend` — wins, then the
    PIPEEDGE_INT8_DECODE_ATTEND env (including an explicit '0' off),
    then the `QuantizeCompute` compute-path config: enabling int8
    compute promotes the decode attend under the measured 'auto' width
    policy (kernel v2 at attend windows <= 256, XLA above). Idempotent
    on already-resolved ints."""
    if override is not None:
        if isinstance(override, str):
            s = override.strip().lower()
            if s == "auto":
                return 3
            if not s or s in ("0", "false", "no", "off"):
                return 0
            return 2 if s == "2" else 1
        return int(override)
    import os
    if os.getenv("PIPEEDGE_INT8_DECODE_ATTEND") is not None:
        return _int8_kernel_env()
    from ..models.layers import quantize_compute
    if quantize_compute().enabled:
        return 3
    return 0


# the measured crossover: kernel v2 beat XLA at attend widths <= 256 in
# every chip session (3/3); XLA won at 1024 in every session. 'auto'
# routes the kernel only below this width.
_INT8_AUTO_MAX_WIDTH = 256


def _use_int8_decode_kernel(bcache: Cache, s: int, cfg: TransformerConfig,
                            width: int, optin: int, batch: int = 1) \
        -> Optional[Tuple[bool, int]]:
    """Route the classic int8 single-token decode step through the fused
    Pallas kernel (ops/decode_attention.py): MHA only (kv_heads == query
    heads), no sliding window, attend window small enough for VMEM —
    GQA/windowed/span/huge-window cases stay on the XLA
    dequantize-then-attend path. Static (trace-time) decision.

    Returns None (use the XLA path) or (interpret, variant): interpret
    True forces interpret mode on a non-TPU backend (tests); variant 1
    is the per-cell grid, 2 the batch-as-sublane grid. `optin` is the
    construction-time resolution of PIPEEDGE_INT8_DECODE_ATTEND
    (`_int8_kernel_env`): an isolated chip microbench measured variant 1
    at parity-to-slower vs XLA's dequantize-then-attend (docs/DECODE.md),
    so the default stays on the XLA path; the kernels are kept,
    exactness-tested, as the experimental base for the fusion."""
    if not optin:
        return None
    if s != 1 or "k_scale" not in bcache:
        return None
    if cfg.kv_heads != cfg.num_attention_heads or cfg.sliding_window:
        return None
    if width * cfg.kv_heads * cfg.head_dim > _INT8_KERNEL_VMEM_CAP:
        return None
    from ..ops.decode_attention import (int8_decode_attention_supported,
                                        int8_v2_fits)
    variant = int(optin)
    if variant == 3:     # 'auto': the measured width-crossover policy
        if width > _INT8_AUTO_MAX_WIDTH or not int8_v2_fits(
                width, batch, cfg.kv_heads, cfg.head_dim):
            return None  # XLA wins at wide windows (3/3 chip sessions)
        variant = 2
    elif variant == 2 and not int8_v2_fits(width, batch, cfg.kv_heads,
                                           cfg.head_dim):
        variant = 1      # v2's whole-batch block can't fit VMEM here
    return (not int8_decode_attention_supported(), variant)



def _block_tail(p: Dict, x: jax.Array, ctx: jax.Array,
                cfg: TransformerConfig, ffn_delta=None) -> jax.Array:
    """Post-attention half of a GPT-2 block (output proj + residual, FFN +
    residual) — shared by the cached decode step, the sp prefill, and the
    ep decode step. `ffn_delta(p, normed) -> delta` overrides the FFN
    (expert-parallel execution plugs in the ep-sharded routed FFN)."""
    x = dense(p["attn_out"], ctx) + x
    normed = layer_norm(p["ln_after"], x, cfg.layer_norm_eps)
    if ffn_delta is not None:
        return x + ffn_delta(p, normed)
    if cfg.n_experts:
        # Capacity routing is NOT causal: a full-sequence forward lets
        # tokens compete for expert slots across the whole sequence, which
        # a cached decode step (routing only the current tokens) cannot
        # reproduce. With capacity_factor >= n_experts (no drops) routing
        # is a pure per-token gate and decode matches the forward exactly;
        # capacity-bounded models route each step's token set on its own.
        from .expert import moe_ffn_delta
        return x + moe_ffn_delta(p["moe"], normed, cfg.n_experts,
                                 cfg.capacity_factor, act=gelu_new)
    return dense(p["mlp_down"], gelu_new(dense(p["mlp_up"], normed))) + x


def _attention_core(p: Dict, x: jax.Array, bcache: LayerCache, pos,
                    cfg: TransformerConfig, prefill: bool,
                    read_len: Optional[int] = None,
                    int8_optin: int = 0) \
        -> Tuple[jax.Array, LayerCache]:
    """ln + qkv + cache update + masked attend: the cached attention half
    shared by the plain and expert-parallel decode steps. `int8_optin` is
    the construction-time PIPEEDGE_INT8_DECODE_ATTEND resolution (bound
    into the stage programs by _make_stage_run): 0 off, 1/2 = forced
    kernel variant, 3 = 'auto' (the measured width-crossover policy —
    see _use_int8_decode_kernel)."""
    normed = layer_norm(p["ln_before"], x, cfg.layer_norm_eps)
    q, k_new, v_new = _qkv(p, normed, cfg)
    w = attend_width(bcache, read_len)
    route = (None if prefill
             else _use_int8_decode_kernel(bcache.stack, x.shape[1], cfg, w,
                                          int8_optin, batch=x.shape[0]))
    if route is not None:
        from ..ops.decode_attention import int8_decode_attention
        interpret, variant = route
        bcache, win = cache_write_quantized(bcache, k_new, v_new, w)
        ctx = int8_decode_attention(
            q, win["k"], win["k_scale"], win["k_shift"], win["v"],
            win["v_scale"], win["v_shift"],
            k_new, v_new, pos, interpret=interpret, variant=variant)
        return ctx, bcache
    k, v, keep, bcache = cache_update_and_read(
        bcache, k_new, v_new, pos, prefill, x.shape[1], q.dtype,
        read_len=read_len)
    return attend(q, k, v, keep, cfg), bcache


def _block_step(p: Dict, x: jax.Array, bcache: LayerCache, pos,
                cfg: TransformerConfig, prefill: bool,
                read_len: Optional[int] = None,
                int8_optin: int = 0) -> Tuple[jax.Array, LayerCache]:
    """One GPT-2 block over current token(s) with cache read/update.

    Prefill: x is the full prompt [B, S, D] written at positions [0, S);
    decode: x is one token [B, 1, D] written at position `pos`. `bcache`
    is the stage's stacked cache and this block's index in it. `read_len`:
    static attend-window truncation (see cache_update_and_read)."""
    ctx, bcache = _attention_core(p, x, bcache, pos, cfg, prefill,
                                  read_len=read_len, int8_optin=int8_optin)
    return _block_tail(p, x, ctx, cfg), bcache


def _block_step_tp(p: Dict, x: jax.Array, bcache: LayerCache, pos,
                   cfg: TransformerConfig, prefill: bool,
                   axis: str, act=gelu_new, ffn_delta=None,
                   read_len: Optional[int] = None) \
        -> Tuple[jax.Array, LayerCache]:
    """Megatron tensor-parallel block step under `shard_map`: the shared
    projection/psum/MLP body from parallel/tensor.py with the attention
    core swapped for a cache-attend over the head-sharded KV cache.
    `ffn_delta` replaces the dense MLP (the tp x ep MoE composition);
    `read_len` is the static bucketed attend window (the position axis is
    unsharded, so truncation is per-shard local)."""
    from .tensor import _tp_block_local

    def cache_attend(q, k_new, v_new):
        nonlocal bcache
        k, v, keep, bcache = cache_update_and_read(
            bcache, k_new, v_new, pos, prefill, x.shape[1], q.dtype,
            read_len=read_len)
        return attend(q, k, v, keep, cfg)      # [b, s, h_local * hd]

    y = _tp_block_local(p, x, cfg, axis, act=act,
                        qkv_to_ctx=cache_attend, ffn_delta=ffn_delta)
    return y, bcache


def single_token_embed(pe: Dict, tok: jax.Array, pos) -> jax.Array:
    """Embed one decode-step token [B] at traced position `pos` ->
    [B, 1, D]: wte row + dynamic-sliced wpe row. THE single-token
    embedding rule — shared by the host stage runner and the SPMD wave
    decoder so they cannot diverge."""
    wpe = jax.lax.dynamic_slice_in_dim(pe["wpe"], pos, 1)
    return jnp.take(pe["wte"], tok.reshape(-1), axis=0)[:, None] + wpe[None]


def span_embed(pe: Dict, tok: jax.Array, pos) -> jax.Array:
    """Embed a K-token span [B, K] at positions [pos, pos+K) ->
    [B, K, D] (the speculative-decoding verify step's embedding;
    K is static, `pos` traced)."""
    wpe = jax.lax.dynamic_slice_in_dim(pe["wpe"], pos, tok.shape[1])
    return jnp.take(pe["wte"], tok, axis=0) + wpe[None]


def stage_blocks(params: Dict) -> jax.Array:
    """The stacked blocks pytree of a decode stage (block-aligned shard),
    or its `BlockRuns` where the blocks are of more than one kind."""
    blocks = params.get("blocks")
    if blocks is None:
        raise ValueError("decode stages must contain full blocks "
                         "(block-aligned partition)")
    if isinstance(blocks, BlockRuns):
        return blocks
    if isinstance(blocks, (tuple, list)):  # unrolled layout -> restack
        blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return blocks


# widths an octave for a batch job (`DecodePipeline.generate`); every other
# caller keeps the powers of two (`attend_bucket`)
JOB_PER_OCTAVE = 4


def job_per_octave(leaves, stages) -> int:
    """Widths an octave worth a program to a batch job on these stages:
    `JOB_PER_OCTAVE` where the window is what most blocks read, half as
    many where fewer than half of the blocks keep a row a position (the
    family's `cache_leaves` say which kinds do; a block whose leaves are
    rings reads its ring whatever the ladder says and counts with those
    that keep a state). A program more is a compile
    in a first run and a load in every later one, and a narrower window
    speeds up only the blocks that attend it: three layers in four of
    qwen3_next keep a state and no window, its span programs are the
    largest (1.2 s a load), and at four an octave its warm set-up grew by
    12% for 4% of tokens/s (PERF.md, PR 34)."""
    rows = {name: leaf for name, leaf in (leaves or {}).items()
            if name != STATS and not getattr(leaf, "whole", False)
            and not getattr(leaf, "length", 0)}
    owner = leaf_owners(rows)
    runs = [run for st in stages for run in st.get("runs") or ()]
    if not rows or len(owner) < len(rows) or not runs:
        return JOB_PER_OCTAVE
    kinds = {kind for kinds in owner.values() for kind in kinds}
    windowed = sum(count for kind, count in runs if kind in kinds)
    return JOB_PER_OCTAVE if 2 * windowed >= sum(
        count for _, count in runs) else JOB_PER_OCTAVE // 2

M_ATTEND = prom.REGISTRY.counter(
    "pipeedge_attend_positions_total",
    "cache positions the dispatched stage programs attend, a row a query: "
    "kind=read the static window compiled for, kind=live the positions "
    "below the call's first; phase=prefill a span, phase=decode a step")
for _phase in ("prefill", "decode"):
    for _kind in ("read", "live"):
        M_ATTEND.declare(phase=_phase, kind=_kind)
M_LEAF_BYTES = prom.REGISTRY.gauge(
    "pipeedge_cache_leaf_bytes",
    "bytes of each leaf of the caches a pipeline made last, over its "
    "stages, where its family names its leaves: a ring's do not grow with "
    "max_len")


def count_stats(names, prompt, steps) -> None:
    """Counts read back from `stats` leaves (`FamilySpec.stats_names`'
    order) onto the registry's `pipeedge_<name>_total`, by phase: what
    prompt passes counted, what decode steps did."""
    for phase, counts in (("prefill", prompt), ("decode", steps)):
        for name, count in zip(names, counts):
            prom.REGISTRY.counter(
                f"pipeedge_{name}_total",
                "counted on the device by the stage programs, read back "
                "once a batch (a server: once a scrape)").inc(
                    float(count), phase=phase)


def attend_bucket(pos_next: int, max_len: int, floor: int = 64,
                  per_octave: int = 1, grain: Optional[int] = None) -> int:
    """Static attend-window size for a decode step with `pos_next` valid
    cache rows: the smallest width >= pos_next on a ladder, capped at
    max_len. The attend matmul and int8 dequant then track the LIVE cache
    length instead of max_len (the longer the max_len headroom, the bigger
    the saving), and every width is one compiled program.

    There are two ladders, because two kinds of caller want opposite things
    of it. `per_octave=1` is the powers of two from `floor`: log2(max_len /
    floor) + 1 programs, few enough for a server to warm every one before
    it takes traffic (a compile inside a live window is a stalled user), at
    the price of a window up to twice the live length. `per_octave=4` adds
    1.25, 1.5 and 1.75 times each power of two: a batch job meets every
    width of its schedule in its first batch and the persistent compile
    cache makes later runs a load, so it takes four times the programs for
    a window at most a quarter longer than it needs. `floor` is the least
    width; `grain` (default `floor`) the least difference between two
    widths, so the low octaves are not cut finer than is worth a program
    (from 64 by 64: 64, 128, 192, 256, 320, 384, 448, 512, 640, ...);
    where `floor` is a whole number of grains, so is every width."""
    if pos_next > max_len:
        raise ValueError(f"pos_next {pos_next} exceeds max_len {max_len}")
    base = floor = max(1, floor)
    while 2 * base < pos_next:
        base *= 2
    if pos_next <= base:
        return min(base, max_len)
    grain = floor if grain is None else max(1, grain)
    step = grain * -(-base // (per_octave * grain))     # whole grains
    width = base + -(-(pos_next - base) // step) * step
    return min(width, 2 * base, max_len)


# bytes of the rows the LONGEST run of blocks leaves for a `whole` leaf from
# which the leaf is written where it lies, a run at a time, and the later
# runs read the stack so written (they read other layers: nothing changes for
# them). The choice is the leaf's, made once before the runs
# (`_written_by_run`): a leaf is placed for all of its runs or gathered from
# all of them, whatever their lengths. Gathered until the end and put in the
# leaf's place, as smaller states are, the runs' rows are a second copy of
# the leaf alive beside the first and a concatenation's bytes a call: 2.76 GB
# of Mamba-2 state at 128 rows of nemotron_h where the states of qwen3_next
# (17 MB a layer at 8 rows), minicpm_sala and lfm2 are noise. A placed leaf
# is written one way in a span and one in a step.
#
# A span (and a step on a backend without Mosaic): every run that writes the
# leaf puts its rows into the donated stack at its own layers as it leaves
# them, a chain of updates of one buffer, each after the last reader of the
# layers it writes, which XLA does in place (since PR 54 a span's run does so
# a block at a time, the stack its scan's carry: `body_placing`). Each run's
# updates are fenced to its output (`optimization_barrier`): left free, the
# chip's compiler put a Mamba-2 layer's update after the NEXT run's grouped
# kernels, and nemotron_h's
# eleven-run step program then computed other hidden states from its third
# Mamba-2 layer on (0.26 of the logits' range from the float32 reference;
# the same program with the tile loop for the kernels, or with the state
# gathered, agreed to 6e-7; eight runs agreed either way; PERF.md, PR 47, and
# section 7). The fence is a workaround, not a cure.
#
# A step (PR 48): the run's blocks are called one after another, not
# scanned, each told which leaves are placed (`LayerCache.placed`), and a
# block may update its layer of such a leaf where it lies and hand back the
# stack, which the next block takes: the Mamba-2 state kernel
# (`ops/ssm_step.py`, the stack aliased in and out, one read and one write
# of a layer where the update above and the `y` beside it were three). The
# kernel's `y` feeds the residual, so data puts it before the next run's
# kernels and it needs no fence; rows a block hands back instead go the
# span's way. `tests/test_chip_compile_families.py` holds the scheduled step
# program to that order either way. ROADMAP D1 asks for one way to update
# every `whole` leaf, which changes the siblings' programs and so needs
# their cells measured
WHOLE_IN_PLACE_BYTES = 1 << 26


def _n_blocks(run) -> int:
    return jax.tree_util.tree_leaves(run)[0].shape[0]


def _written_by_run(runs, kinds, cache: Cache, leaves) -> tuple:
    """The `whole` leaves that `_run_blocks` writes a run at a time: those
    whose longest run leaves `WHOLE_IN_PLACE_BYTES` of rows or more (a
    layer's rows of a `whole` leaf are the layer)."""
    owner = leaf_owners(leaves)

    def longest(name):      # in blocks, of the runs that write the leaf
        return max((_n_blocks(run) for run, kind in zip(runs, kinds)
                    if kind in owner.get(name, (kind,))), default=0)

    return tuple(
        name for name in whole_names(leaves) if name in cache
        and longest(name) * math.prod(cache[name].shape[1:])
        * cache[name].dtype.itemsize >= WHOLE_IN_PLACE_BYTES)


def _run_blocks(blocks, x, cache: Cache, pos, cfg: TransformerConfig,
                prefill: bool, block_fn=_block_step,
                whole: tuple = (), kinds: tuple = (),
                leaves=None, write=None) -> Tuple[jax.Array, Cache]:
    """Scan the stage's blocks over x: one scan a run of like blocks (a
    bare stacked pytree is one run; `BlockRuns`, a dense layer before
    expert layers, several), all over the one cache stack, a run's blocks
    at the layers that follow the run before. Where leaves of the cache
    belong to kinds of block (`leaves`, the family's `cache_leaves`, and
    `kinds`, the kind of each run), a run sees its own kind's leaves and
    those of no kind, and its blocks are at the layers that follow the
    earlier runs OF THE KINDS THAT OWN ITS LEAVES: in a stage of three
    linear blocks, a full one, three linear and a full, the linear runs are
    at layers 0-2 and 3-5 of their leaves and the full ones at 0 and 1 of
    theirs; where a leaf belongs to two kinds (one mixer before two kinds
    of FFN: two runs, one leaf) the second kind's run follows the first's
    in it (`shares_layers`). The scan only READS the stacked cache (each block its layer's window) and stacks the blocks'
    new rows; one update a leaf then writes them where the donated buffer's
    layout is the program's own. A stack of rows a position must not be
    the scan's carry: the TPU compiler lays a carried buffer out to suit
    the rows written into it and copies the whole cache into and out of
    that layout around the loop (PERF.md, PR 25). Block leaves named in
    `whole` are not
    scanned over either: the block step gets each as a `LayerSlice`. A
    `whole` leaf whose rows of its longest run are `WHOLE_IN_PLACE_BYTES` or
    more is written a run at a time, by every run, and not gathered
    (`_written_by_run`): a span's run carries it through its scan and
    writes a layer as its block leaves it; in a step the runs that own one
    are not scanned but unrolled, and their blocks may write it themselves
    (the comment over `WHOLE_IN_PLACE_BYTES`).

    `write(cache, rows)`, where given, writes the blocks' rows in place of
    `write_rows` at `pos`: the step whose rows stand each at its own
    position (parallel/decode_rows.py), whose `pos` is a `RowsAt` that the
    block step reads and this function only hands on."""
    runs = blocks.runs if isinstance(blocks, BlockRuns) else (blocks,)
    kinds = kinds or (None,) * len(runs)
    owner = leaf_owners(leaves)
    placed = _written_by_run(runs, kinds, cache, leaves)
    step = not prefill and x.shape[1] == 1
    rows, done, of_kind = [], 0, {}
    for run, kind in zip(runs, kinds):
        view, first = cache, done
        if owner:       # this kind's leaves, at their owners' layers
            view = {name: buf for name, buf in cache.items()
                    if kind in owner.get(name, (kind,))}
            sharing = shares_layers(owner, kind)
            if sharing:
                first = sum(of_kind.get(other, 0) for other in sharing)
        held = {name: run[name] for name in whole if name in run}
        if held:
            run = {name: leaf for name, leaf in run.items()
                   if name not in held}
        owned = tuple(name for name in placed if name in view)
        mine = owned if step else ()

        def block(y, bp, layer, view, held=held, first=first, mine=mine):
            if held:
                bp = dict(bp, **{name: LayerSlice(leaf, layer)
                                 for name, leaf in held.items()})
            at = first + layer if first else layer      # the cache's layer
            return block_fn(bp, y, LayerCache(view, at, placed=mine), pos,
                            cfg, prefill)

        def body(y, xs, view=view):
            y, bc = block(y, *xs, view)
            return y, bc.rows

        def body_placing(carry, xs, view=view, first=first):
            # a span of a run that owns placed leaves: the stacks are the
            # scan's carry and each block's rows go into them at its layer
            # as it leaves them (a layer whole, in the stack's own layout:
            # nothing for the compiler to lay out anew, PR 25's copy was of
            # rows a position), so no run's rows are gathered beside the
            # stack: nine layers of granite_hybrid's state are 1.2 GB
            y, stacks = carry
            y, bc = block(y, *xs, dict(view, **stacks))
            rows = dict(bc.rows)
            at = (first + xs[1],)
            stacks = {name: jax.lax.dynamic_update_slice(
                buf, rows.pop(name)[None].astype(buf.dtype),
                at + (0,) * (buf.ndim - 1)) for name, buf in stacks.items()}
            return (y, stacks), rows

        n_blocks = _n_blocks(run)
        if owned and not step:
            (x, stacks), new = jax.lax.scan(
                body_placing, (x, {name: cache[name] for name in owned}),
                (run, jnp.arange(n_blocks)))
            # done before the next run (the comment over
            # `WHOLE_IN_PLACE_BYTES`)
            x, stacks = jax.lax.optimization_barrier((x, stacks))
            cache = dict(cache, **stacks)
        elif mine:
            # a step of a run that owns placed leaves: its blocks one after
            # another, not scanned, so that a block may update its layer of
            # such a leaf where it lies and hand the stack to the next (a
            # scan would carry the stack: PERF.md, PR 25); the rows of the
            # blocks that did not are stacked as the scan stacks them
            new = []
            for layer in range(n_blocks):
                x, bc = block(x, jax.tree_util.tree_map(
                    lambda leaf, layer=layer: leaf[layer], run), layer, view)
                view = bc.stack
                new.append(bc.rows)
            cache = dict(cache, **{name: view[name] for name in mine})
            new = {name: jnp.stack([rows[name] for rows in new])
                   for name in new[0]}
        else:
            x, new = jax.lax.scan(body, x, (run, jnp.arange(n_blocks)))
        for name in placed:
            if name not in new:
                continue
            new = dict(new)
            # written at the run's layers, and done before the next run
            x, written = jax.lax.optimization_barrier((
                x, jax.lax.dynamic_update_slice(
                    cache[name], new.pop(name).astype(cache[name].dtype),
                    (first,) + (0,) * (cache[name].ndim - 1))))
            cache = dict(cache, **{name: written})
        rows.append(new)
        done += n_blocks
        of_kind[kind] = of_kind.get(kind, 0) + n_blocks
    # a leaf's rows, from the runs that wrote it, in the model's order (a
    # leaf of a kind the stage has no block of has none, and no layers)
    rows = rows[0] if len(rows) == 1 else {
        name: jnp.concatenate(parts) for name in cache
        if (parts := [new[name] for new in rows if name in new])}
    rest = {name: buf for name, buf in cache.items()
            if name not in placed} if placed else cache
    written = write(rest, rows) if write is not None else write_rows(
        rest, rows, 0 if prefill else pos, whole=whole_names(leaves),
        rings=ring_names(leaves), strides=stride_names(leaves))
    return x, dict(written, **{name: cache[name] for name in placed})


# every stage program takes (params, data, cache[, pos]) and donates the
# cache: XLA aliases it to the returned cache, so the rows are written in
# place and a step holds one copy of the cache, not two
_DONATE_CACHE = (2,)


def make_stage_fns(family, cfg: TransformerConfig, shard_config: ShardConfig,
                   int8_optin=None):
    """(prefill_fn, decode_fn) for one block-aligned pipeline stage.

    prefill_fn(params, data, cache)        -> (out, cache)   data: ids|hidden
    decode_fn(params, data, cache, pos)    -> (out, cache)   data: ids|hidden

    Both DONATE `cache`: the caller rebinds and drops the old reference.

    First stage embeds token ids (decode positions offset by `pos`); last
    stage applies the final LN + LM head and returns per-token logits.
    `int8_optin` is the resolved int8 decode-attend routing
    (`_resolve_int8_optin`; None re-resolves from env/config).
    """
    run = _make_stage_run(family, cfg, shard_config, int8_optin=int8_optin)

    # plain functions, not partials: jit names the compiled module, and
    # with it every row of a profiler trace, by `__name__`
    def prefill(params, data, cache):
        return run(params, data, cache, pos=0, prefill=True)

    def decode_step(params, data, cache, pos, read_len=None,
                    last_only=False):
        return run(params, data, cache, pos, prefill=False,
                   read_len=read_len, last_only=last_only)

    prefill_fn = jax.jit(prefill, donate_argnums=_DONATE_CACHE)
    # read_len is STATIC: each attend-window bucket compiles its own
    # decode-step program (a handful of variants off `attend_bucket`'s
    # ladder, the same compile-per-discrete-value pattern as the quantized
    # edge bitwidths)
    # so is last_only: a span of a prompt prefilled in spans gives only its
    # last row to the head
    decode_fn = jax.jit(decode_step,
                        static_argnames=("read_len", "last_only"),
                        donate_argnums=_DONATE_CACHE)
    return prefill_fn, decode_fn


def run_geometry(family, cfg: TransformerConfig,
                 shard_config: ShardConfig) -> Dict:
    """What `_run_blocks` is told of one stage's blocks, as its keywords:
    the block leaves handed whole, the kind of each run, the family's cache
    leaves."""
    leaves = getattr(family, "cache_leaves", None)
    return dict(
        whole=tuple(getattr(family, "whole_leaves", ())),
        kinds=tuple(kind for kind, _ in kind_runs(family, cfg, shard_config)),
        leaves=leaves(cfg) if leaves is not None else None)


def _make_stage_run(family, cfg: TransformerConfig,
                    shard_config: ShardConfig, block_fn=None,
                    finalize_fn=None, embed_fn=None, int8_optin=None):
    plan = plan_shard(shard_config)
    if plan.head is not None or plan.tail is not None:
        raise ValueError("decode requires a block-aligned partition "
                         f"(layers [{shard_config.layer_start}, "
                         f"{shard_config.layer_end}] cut mid-block)")
    if block_fn is None:
        # family-dispatched cached block (llama supplies RoPE/GQA/SwiGLU);
        # the default is the GPT-2-shaped step, with the int8-kernel
        # opt-in resolved HERE — at stage-program construction
        # (DecodePipeline.__init__) — so toggling the env var after
        # programs compile cannot leave stale shapes on the old setting
        block_fn = getattr(family, "cached_block_step", None)
        if block_fn is None:
            block_fn = partial(_block_step,
                               int8_optin=_resolve_int8_optin(int8_optin))

    geometry = run_geometry(family, cfg, shard_config)

    def run(params, data, cache, pos, prefill, read_len=None,
            last_only=False):
        if shard_config.is_first:
            if embed_fn is not None:
                data = embed_fn(params["embeddings"], data)
            elif prefill:
                data = family.embed(params["embeddings"], data, cfg)
            elif data.ndim == 2 and data.shape[1] > 1:
                # span step (speculative verify): K tokens at [pos, pos+K)
                tok_embed = getattr(family, "span_embed", None) or span_embed
                data = tok_embed(params["embeddings"], data, pos)
            else:
                tok_embed = getattr(family, "decode_embed", None) \
                    or single_token_embed
                data = tok_embed(params["embeddings"], data, pos)
        # bind the static attend window only when bucketing is active —
        # the ep block step is the one variant without the kwarg, and its
        # path never binds a bucket (DecodePipeline._bucketed)
        bf = block_fn if read_len is None \
            else partial(block_fn, read_len=read_len)
        data, cache = _run_blocks(stage_blocks(params), data, cache, pos,
                                  cfg, prefill, block_fn=bf, **geometry)
        if shard_config.is_last:
            if last_only:
                data = data[:, -1:]
            data = (finalize_fn or family.finalize)(params["final"], data,
                                                    cfg)
        return data, cache

    return run


def _tp_shards_head(cfg: TransformerConfig, n: int) -> bool:
    """Vocab-shard the LM head when the tp degree divides the vocab size —
    at decode the head matmul is a third of GPT-2's per-token FLOPs, so
    leaving it replicated would cap the tp speedup around 3x. A
    non-divisible combination (e.g. gpt2's 50257 at tp=2/4/8) falls back
    to a replicated head."""
    return cfg.vocab_size > 0 and n > 1 and cfg.vocab_size % n == 0


def tp_param_specs(params: Dict, cfg: TransformerConfig, n: int,
                   axis: str = "tp"):
    """Partition-spec pytree for one decode stage's params under Megatron
    TP (degree `n`): blocks per the family spec table (leading block axis
    replicated), embeddings replicated, LM head vocab-sharded when
    divisible (`_tp_shards_head`)."""
    from jax.sharding import PartitionSpec as P

    from .tensor import _rename_axis, family_tp_plan
    table, _ = family_tp_plan(cfg)
    table = _rename_axis(table, axis)
    specs = {k: jax.tree_util.tree_map(lambda _: P(), v)
             for k, v in params.items() if k != "blocks"}
    specs["blocks"] = jax.tree_util.tree_map(
        lambda _, s: P(*((None,) + tuple(s))), params["blocks"], table)
    if "final" in params and "head" in params["final"] \
            and _tp_shards_head(cfg, n):
        specs["final"]["head"] = {"w": P(None, axis), "b": P(axis)}
    return specs


def tp_vocab_head_finalize(pf: Dict, hidden, cfg: TransformerConfig,
                           axis: str, norm_fn):
    """Vocab-sharded LM head under tp — THE shared finalize for tp decode
    stages: `norm_fn` (layer_norm for GPT-2, rms_norm for llama) runs
    replicated, the head matmul produces local logit slices, one tiled
    all_gather restores the full [B, S, V]."""
    hidden = norm_fn(pf["ln"], hidden, cfg.layer_norm_eps)
    y = jnp.dot(hidden, pf["head"]["w"].astype(hidden.dtype),
                preferred_element_type=jnp.float32) + pf["head"]["b"]
    return jax.lax.all_gather(y.astype(hidden.dtype), axis,
                              axis=y.ndim - 1, tiled=True)


def tp_cache_specs(cache: Cache, axis: str = "tp"):
    """Head-shard the cache leaves: axis 3 of the K/V buffers
    [L, B, T, H, Dh] AND of the per-head scale/shift rows [L, B, T, H]
    (the head axis on the scales is what lets int8 caches compose with
    tp — each device quantizes/dequantizes its own head slice)."""
    from jax.sharding import PartitionSpec as P
    return {k: P(*([None, None, None, axis]
                   + [None] * (v.ndim - 4))) for k, v in cache.items()}


def make_tp_stage_fns(family, cfg: TransformerConfig,
                      shard_config: ShardConfig, mesh, params: Dict,
                      axis: str = "tp", cache_bits: int = 0):
    """Tensor-parallel variant of `make_stage_fns`: the stage executes under
    `shard_map` over `axis` with head-sharded KV cache and the 2-psum
    Megatron block body — decode-step latency scales with the tp degree.
    `params` (stacked-blocks layout) supplies the pytree structure for the
    partition specs; `cache_bits=8` composes int8 caches with tp (the
    per-head scale rows shard over `axis` with the K/V buffers)."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    if cfg.num_attention_heads % n or cfg.kv_heads % n:
        raise ValueError(f"tp={n} requires head count "
                         f"({cfg.num_attention_heads}) and kv head count "
                         f"({cfg.kv_heads}) divisible by tp")
    if cfg.n_experts:
        raise NotImplementedError(
            "tensor-parallel decode does not cover MoE blocks (experts "
            "shard over 'ep', not 'tp') — use make_tp_ep_stage_fns / "
            "DecodePipeline(tp_ep_mesh=...) for the tp x ep composition")
    fam_tp_step = getattr(family, "tp_cached_block_step", None)
    if fam_tp_step is None \
            and getattr(family, "cached_block_step", None) is not None:
        raise NotImplementedError(
            f"tensor-parallel decode pairs the default (GPT-2-shaped) "
            f"cached step with the Megatron body; the {family.name} "
            "family supplies a custom cached block step but no tp variant "
            "(forward TP — make_tp_block_fn / --spmd-tp — does cover it)")

    fam_tp_fin = getattr(family, "tp_finalize", None)
    fin = None
    if _tp_shards_head(cfg, n):
        fin = partial(fam_tp_fin, axis=axis) if fam_tp_fin \
            else partial(tp_vocab_head_finalize, axis=axis,
                         norm_fn=layer_norm)
    run = _make_stage_run(family, cfg, shard_config,
                          block_fn=partial(fam_tp_step or _block_step_tp,
                                           axis=axis),
                          finalize_fn=fin)
    p_specs = tp_param_specs(params, cfg, n, axis)
    c_specs = tp_cache_specs(init_cache(cfg, 1, 1, 1,
                                        cache_bits=cache_bits), axis)

    def tp_prefill(params, data, cache):
        return run(params, data, cache, pos=0, prefill=True)

    prefill_fn = jax.jit(jax_compat.shard_map(
        tp_prefill, mesh=mesh,
        in_specs=(p_specs, P(), c_specs), out_specs=(P(), c_specs)),
        donate_argnums=_DONATE_CACHE)

    # the bucketed attend window is bound into the shard_map closure per
    # static read_len value — jit re-traces per bucket, same
    # compile-per-discrete-value pattern as the plain path
    @partial(jax.jit, static_argnames=("read_len",),
             donate_argnums=_DONATE_CACHE)
    def tp_decode_step(params, data, cache, pos, read_len=None):
        return jax_compat.shard_map(
            partial(run, prefill=False, read_len=read_len), mesh=mesh,
            in_specs=(p_specs, P(), c_specs, P()),
            out_specs=(P(), c_specs))(
                params, data, cache, pos)

    # p_specs is returned so callers place params with the SAME specs the
    # program compiled against (drift would silently reshard every call)
    return prefill_fn, tp_decode_step, p_specs


def validate_partition(partition: Sequence[Tuple[int, int]],
                       total: int) -> None:
    """Require `partition` to contiguously cover [1, total] in order."""
    expect = 1
    for l, r in partition:
        if l != expect:
            raise ValueError(f"partition {list(partition)} does not "
                             f"contiguously cover [1, {total}]")
        expect = r + 1
    if expect != total + 1:
        raise ValueError(f"partition {list(partition)} does not "
                         f"contiguously cover [1, {total}]")


def round_partition_to_blocks(partition: Sequence[Tuple[int, int]],
                              total: int) -> List[Tuple[int, int]]:
    """Round a sublayer-granular partition (e.g. from the native
    sched-pipeline scheduler, which cuts at quarter-block granularity) to
    the block-aligned cuts decoding requires: each interior cut moves to
    the nearest block boundary (multiple of 4; a cut exactly halfway
    between boundaries rounds UP — an explicit tie rule, where Python's
    round() would banker's-round to the even block), empty stages are
    dropped. Coverage of [1, total] is preserved."""
    if total % 4:
        raise ValueError(f"total sublayers {total} not a multiple of 4")
    cuts = [r for (_, r) in partition[:-1]]
    rounded = sorted({min(total - 4, max(4, int(c / 4 + 0.5) * 4))
                      for c in cuts})
    bounds = [0] + [c for c in rounded if c < total] + [total]
    return [(bounds[i] + 1, bounds[i + 1]) for i in range(len(bounds) - 1)
            if bounds[i + 1] > bounds[i]]


def validate_capacity(cfg: TransformerConfig, max_len: int,
                      prompt_len: int = 0, new_tokens: int = 0) -> None:
    """Reject cache/position overflows up front: dynamic_update_slice
    clamps out-of-range starts, so an overflow would silently corrupt the
    last cache row instead of erroring."""
    if cfg.max_position_embeddings and max_len > cfg.max_position_embeddings:
        raise ValueError(f"max_len {max_len} exceeds the model's "
                         f"{cfg.max_position_embeddings} positions")
    if prompt_len + new_tokens > max_len:
        raise ValueError(f"prompt {prompt_len} + {new_tokens} new tokens "
                         f"exceeds max_len {max_len}")


def _repeat_batch(tree, k: int):
    """Tile the batch axis (axis 1 of [L, B, ...] cache leaves) k times:
    beam b of batch i occupies row i*k + b."""
    return {name: x if name == STATS else jnp.repeat(x, k, axis=1)
            for name, x in tree.items()}


def _gather_batch(tree, rows: jax.Array):
    """Reorder the batch axis of cache leaves by `rows` [B*k]."""
    return {name: x if name == STATS else jnp.take(x, rows, axis=1)
            for name, x in tree.items()}


@partial(jax.jit, static_argnames=("temperature", "top_k"))
def _pick_token(logits, rng, temperature: float, top_k: int):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.float32(temperature)
    if top_k > 0:
        # keep EXACTLY top_k candidates: scatter the top_k values back by
        # index. A threshold compare (scaled >= kth) admits every logit
        # tied with the k-th value, growing the candidate set on ties.
        vals, idx = jax.lax.top_k(scaled, top_k)
        rows = jnp.arange(scaled.shape[0])[:, None]
        scaled = jnp.full_like(scaled, -jnp.inf).at[rows, idx].set(vals)
    return jax.random.categorical(rng, scaled, axis=-1)


def make_token_picker(temperature: float = 0.0, top_k: int = 0):
    """`pick(logits [B, V], rng) -> tokens [B]`: greedy argmax at
    temperature 0, else categorical sampling over logits/temperature,
    optionally truncated to exactly the `top_k` most likely (ties at the
    k-th value broken by index order, matching `jax.lax.top_k`).

    Binds a module-level jitted function with static (temperature, top_k),
    so repeated generate() calls with the same settings hit the jit cache
    instead of retracing a fresh closure."""
    return partial(_pick_token, temperature=float(temperature),
                   top_k=int(top_k))


@partial(jax.jit, static_argnames=("temperature", "top_k"))
def pick_next(out, rng, temperature: float, top_k: int):
    rng, sub = jax.random.split(rng)
    token = _pick_token(out[:, -1].astype(jnp.float32), sub,
                        temperature=temperature, top_k=top_k)
    return token, token[:, None], rng


def _pick_last(out, rng, temperature: float, top_k: int):
    if out.shape[1] > 1:
        # a prompt pass or a span: its last position is cut out here, one
        # eager dispatch a request, so that `pick_next` compiles for the
        # step's shape alone (half a second a prompt length on the chip)
        out = jax.lax.slice_in_dim(out, out.shape[1] - 1, None, axis=1)
    return pick_next(out, rng, temperature=temperature, top_k=top_k)


@jax.jit
def join_tokens(ids, *tokens):
    """A batch's result: the prompts `[B, S]` with the tokens picked, each
    `[B]`, beside them, in one program a (batch, prompt, token count): an
    eager `stack` is a dispatch a token after the last step, with the
    device idle under them (PERF.md section 6, PR 49)."""
    return jnp.concatenate([ids, jnp.stack(tokens, axis=1)], axis=1)


def make_next_picker(temperature: float = 0.0, top_k: int = 0):
    """`pick(out [B, S, V], rng) -> (tokens [B], ids [B, 1], rng)`: one
    program between two stage programs. From the last stage's output
    (prompt pass, span or step) and the stream's key it returns the picked
    token (`make_token_picker`'s rule on the last position's float32
    logits, under one split of the key), the same token shaped as the next
    step's input, and the key for the next pick. `generate` and both
    decode executors pick with it, so their streams stay token-identical,
    and a token costs the host one dispatch here: the slice, the split,
    the cast and the reshape, made eagerly, cost one each."""
    return partial(_pick_last, temperature=float(temperature),
                   top_k=int(top_k))


def make_ep_stage_fns(family, cfg: TransformerConfig,
                      shard_config: ShardConfig, mesh, params: Dict,
                      axis: str = "ep", cache_bits: int = 0):
    """Expert-parallel variant of `make_stage_fns` for MoE stages: the
    routed FFN's experts shard over `axis` (each device computes its local
    experts' tokens, one psum combines — parallel/expert.py's layout inside
    the decode step). Attention and the KV cache are replicated across the
    ep axis (experts hold the dominant parameter mass in an MoE decoder).
    Returns (prefill_fn, decode_fn, param_specs) — place params with the
    returned specs."""
    from jax.sharding import PartitionSpec as P

    from .expert import ep_ffn_delta

    if not cfg.n_experts:
        raise ValueError("make_ep_stage_fns requires an MoE config "
                         "(cfg.n_experts > 0)")
    n = mesh.shape[axis]
    if cfg.n_experts % n:
        raise ValueError(f"ep={n} must divide n_experts ({cfg.n_experts})")

    def ffn_delta(p, normed):
        return ep_ffn_delta(p["moe"], normed, cfg.n_experts,
                            cfg.capacity_factor, axis, act=gelu_new)

    # kernel opt-in resolved at stage-fn construction, same rule as
    # _make_stage_run (the int8-cache MHA ep composition routes too)
    int8_optin = _int8_kernel_env()

    def block_step_ep(p, x, bcache, pos, cfg_, prefill):
        ctx, bcache = _attention_core(p, x, bcache, pos, cfg_, prefill,
                                      int8_optin=int8_optin)
        return _block_tail(p, x, ctx, cfg_, ffn_delta=ffn_delta), bcache

    run = _make_stage_run(family, cfg, shard_config, block_fn=block_step_ep)
    # experts shard on their leading axis (under the stacked block axis);
    # everything else — attention weights, cache (incl. int8 scale rows:
    # replicated cache means identical quantization on every device) —
    # replicated
    p_specs = jax.tree_util.tree_map(lambda _: P(), params)
    p_specs["blocks"]["moe"]["experts"] = jax.tree_util.tree_map(
        lambda _: P(None, axis), params["blocks"]["moe"]["experts"])
    c_specs = {k: P() for k in init_cache(cfg, 1, 1, 1,
                                          cache_bits=cache_bits)}

    def ep_prefill(params, data, cache):
        return run(params, data, cache, pos=0, prefill=True)

    def ep_decode_step(params, data, cache, pos):
        return run(params, data, cache, pos, prefill=False)

    prefill_fn = jax.jit(jax_compat.shard_map(
        ep_prefill, mesh=mesh,
        in_specs=(p_specs, P(), c_specs), out_specs=(P(), c_specs)),
        donate_argnums=_DONATE_CACHE)
    decode_fn = jax.jit(jax_compat.shard_map(
        ep_decode_step, mesh=mesh,
        in_specs=(p_specs, P(), c_specs, P()), out_specs=(P(), c_specs)),
        donate_argnums=_DONATE_CACHE)
    return prefill_fn, decode_fn, p_specs


def make_tp_ep_stage_fns(family, cfg: TransformerConfig,
                         shard_config: ShardConfig, mesh, params: Dict,
                         tp_axis: str = "tp", ep_axis: str = "ep"):
    """The MoE serving composition: attention tensor-parallel over
    `tp_axis` AND experts expert-parallel over `ep_axis`, in ONE mesh and
    one shard_map program per stage.

    This is the layout a real MoE serving stack needs — attention (and its
    KV cache) head-sharded so decode-step latency scales with tp, experts
    sharded so the dominant parameter mass splits across ep — and it is
    exact: attention psums over tp reproduce the dense result, routing
    sees the full (replicated) token set so top-1 capacity semantics are
    untouched, and the expert psum over ep adds exactly one nonzero term
    per token (parallel/expert.py). Cache rows shard over tp and
    replicate over ep; embeddings, router, and LM head stay replicated.

    Returns (prefill_fn, decode_fn, param_specs) — place params with the
    returned specs. int8 caches are excluded for the same per-device
    scale-row reason as plain tp decode."""
    from jax.sharding import PartitionSpec as P

    from .expert import ep_ffn_delta
    from .tensor import _rename_axis, family_tp_ep_plan

    if not cfg.n_experts:
        raise ValueError("make_tp_ep_stage_fns requires an MoE config "
                         "(cfg.n_experts > 0); use make_tp_stage_fns for "
                         "dense models")
    ntp, nep = mesh.shape[tp_axis], mesh.shape[ep_axis]
    if cfg.num_attention_heads % ntp:
        raise ValueError(f"tp={ntp} requires head count "
                         f"({cfg.num_attention_heads}) divisible by tp")
    if cfg.n_experts % nep:
        raise ValueError(f"ep={nep} must divide n_experts "
                         f"({cfg.n_experts})")
    # single family-dispatch point (tensor.py), like family_tp_plan for
    # dense TP: attention spec table + the family's FFN activation
    fam_specs, act = family_tp_ep_plan(cfg)

    def ffn_delta(p, normed):
        return ep_ffn_delta(p["moe"], normed, cfg.n_experts,
                            cfg.capacity_factor, ep_axis, act=act)

    # the tp decode block step, with the dense MLP swapped for the
    # ep-sharded routed FFN — one cache-attend implementation for both
    run = _make_stage_run(family, cfg, shard_config,
                          block_fn=partial(_block_step_tp, axis=tp_axis,
                                           act=act, ffn_delta=ffn_delta))

    # blocks: attention per the family's Megatron spec table over tp
    # (stacked block axis leading), router replicated, expert slabs over ep
    att_specs = _rename_axis(fam_specs, tp_axis)
    p_specs = {k: jax.tree_util.tree_map(lambda _: P(), v)
               for k, v in params.items() if k != "blocks"}
    bspecs = {}
    for k, v in params["blocks"].items():
        if k == "moe":
            bspecs[k] = {
                "router": jax.tree_util.tree_map(lambda _: P(None),
                                                 v["router"]),
                "experts": jax.tree_util.tree_map(
                    lambda _: P(None, ep_axis), v["experts"]),
            }
        else:
            bspecs[k] = jax.tree_util.tree_map(
                lambda _, s: P(*((None,) + tuple(s))), v, att_specs[k])
    p_specs["blocks"] = bspecs
    # same head-axis convention _fresh_caches places with (tp_cache_specs)
    c_specs = tp_cache_specs(init_cache(cfg, 1, 1, 1), tp_axis)

    def tp_ep_prefill(params, data, cache):
        return run(params, data, cache, pos=0, prefill=True)

    def tp_ep_decode_step(params, data, cache, pos):
        return run(params, data, cache, pos, prefill=False)

    prefill_fn = jax.jit(jax_compat.shard_map(
        tp_ep_prefill, mesh=mesh,
        in_specs=(p_specs, P(), c_specs), out_specs=(P(), c_specs)),
        donate_argnums=_DONATE_CACHE)
    decode_fn = jax.jit(jax_compat.shard_map(
        tp_ep_decode_step, mesh=mesh,
        in_specs=(p_specs, P(), c_specs, P()), out_specs=(P(), c_specs)),
        donate_argnums=_DONATE_CACHE)
    return prefill_fn, decode_fn, p_specs


def make_sp_prefill_fn(family, cfg: TransformerConfig,
                       shard_config: ShardConfig, mesh, axis: str = "sp",
                       sp_kind: str = "ring"):
    """Sequence-parallel prefill for decoding: the O(S^2) prompt pass —
    the long-context bottleneck — runs with activations sequence-sharded
    over `axis` and an exact causal attention core per block chosen by
    `sp_kind` (parallel/sequence.py::resolve_sp_core — 'ring' streams K/V
    chunks via ppermute with blockwise softmax and skips ring steps
    outside a sliding window, the long-context choice; 'ulysses'
    all-to-all reshards heads<->sequence with blockwise local attention
    and requires heads divisible by the sp degree). Sliding-window
    families (Mistral) bind cfg.sliding_window into the core, so sp
    prefill is windowed exactly like the non-sp path. Each block's K/V
    rows are all-gathered into the stage
    cache, which comes back replicated so the per-token decode steps run
    unchanged. Stage edges carry only the local sequence chunk.

    Requires a block-aligned stage and prompt length divisible by the sp
    degree. MoE stages are covered when routing is dropless
    (capacity_factor >= n_experts — then routing is a per-token gate and
    chunk-local execution is exact); capacity-bounded MoE refuses."""
    from jax.sharding import PartitionSpec as P

    from .sequence import resolve_sp_core

    if cfg.n_experts and cfg.capacity_factor < cfg.n_experts:
        # dropless MoE (capacity_factor >= n_experts) routes as a pure
        # per-token gate, so chunk-local routing is exact and the default
        # block path below covers it; a capacity-BOUNDED router competes
        # tokens for expert slots across the whole sequence, which
        # chunk-local capacity cannot reproduce
        raise NotImplementedError(
            "sequence-parallel prefill covers dropless MoE only "
            "(capacity_factor >= n_experts); capacity-bounded routing "
            "is sequence-global and would change drop semantics per chunk")
    fam_sp_block = getattr(family, "sp_prefill_block_step", None)
    if getattr(family, "position_dependent_attention", False) \
            and fam_sp_block is None:
        raise NotImplementedError(
            f"sequence-parallel prefill does not cover the {family.name} "
            "family (its attention is position-dependent — RoPE — and it "
            "supplies no sp_prefill_block_step hook to pre-rotate at "
            "global chunk positions)")
    n = mesh.shape[axis]
    # Mistral-style models bind their sliding window into the core: the
    # ring schedule then SKIPS K/V blocks wholly behind every local
    # query's window (sequence.py::ring_attention n_steps bound)
    core = resolve_sp_core(sp_kind, cfg.num_attention_heads, n,
                           window=cfg.sliding_window or None)

    def cache_gather(bcache, k_new, v_new):
        """All-gather this chunk's K/V rows into the (replicated) stage
        cache — shared by the default and family sp block steps."""
        k_full, v_full = (jax.lax.all_gather(new, axis, axis=1, tiled=True)
                          for new in (k_new, v_new))
        # rows with no read: the window it also returns is dead code
        return cache_update_and_read(bcache, k_full, v_full, 0, True,
                                     k_full.shape[1], k_full.dtype)[3]

    if fam_sp_block is not None:
        def block_prefill(p, x, bcache, pos, cfg_, prefill):
            return fam_sp_block(p, x, bcache, cfg_, axis, core,
                                cache_gather)
    else:
        def block_prefill(p, x, bcache, pos, cfg_, prefill):
            """One block over the local chunk [B, S/n, D]: causal ring/
            Ulysses attention for the output, all-gathered K/V into the
            cache; the post-attention half is the shared _block_tail."""
            normed = layer_norm(p["ln_before"], x, cfg_.layer_norm_eps)
            q, k_new, v_new = _qkv(p, normed, cfg_)
            ctx = core(q, k_new, v_new, axis, causal=True)
            b, s_local, h, hd = q.shape
            x = _block_tail(p, x, ctx.reshape(b, s_local, h * hd), cfg_)
            return x, cache_gather(bcache, k_new, v_new)

    def sp_embed(pe, ids):
        """Embed this device's prompt chunk at its global positions
        (learned position table added only for families that have one —
        RoPE families carry positions in the attention rotation)."""
        idx = jax.lax.axis_index(axis)
        chunk = ids.shape[1] // n
        local = jax.lax.dynamic_slice_in_dim(ids, idx * chunk, chunk, 1)
        out = jnp.take(pe["wte"], local, axis=0)
        if "wpe" in pe:
            wpe = jax.lax.dynamic_slice_in_dim(pe["wpe"], idx * chunk, chunk)
            out = out + wpe[None]
        return out

    def sp_finalize(pf, hidden, cfg_):
        hidden = jax.lax.all_gather(hidden, axis, axis=1, tiled=True)
        return family.finalize(pf, hidden, cfg_)

    run = _make_stage_run(family, cfg, shard_config, block_fn=block_prefill,
                          finalize_fn=sp_finalize, embed_fn=sp_embed)
    edge_in = P() if shard_config.is_first else P(None, axis)
    edge_out = P() if shard_config.is_last else P(None, axis)

    def sp_prefill(params, data, cache):
        return run(params, data, cache, pos=0, prefill=True)

    return jax.jit(jax_compat.shard_map(
        sp_prefill, mesh=mesh,
        in_specs=(P(), edge_in, P()), out_specs=(edge_out, P())),
        donate_argnums=_DONATE_CACHE)


def build_decode_pipeline(model_name: str,
                          partition: Optional[Sequence] = None,
                          max_len: int = 1024, dtype=jnp.float32,
                          cache_bits: int = 0, attend_floor: int = 64,
                          model_file: Optional[str] = None,
                          stage_params: Optional[Sequence] = None,
                          **pipe_kw) -> "DecodePipeline":
    """Registry-driven `DecodePipeline` construction — THE shared build
    path for the CLIs (tools/generate.py, tools/serve.py, the benchmark),
    so model lookup, per-stage weight loading, and the position-capacity
    clamp cannot drift between tools. `stage_params` supplies already-
    loaded per-stage pytrees (callers that also need them for other
    drivers); extra kwargs (mesh=/sp_mesh=/ep_mesh=/tp_ep_mesh=/devices=/
    int8_decode_attend=) pass through."""
    from ..models import registry
    prom.count_jax_compiles()
    cfg = registry.get_model_config(model_name)
    total = registry.get_model_layers(model_name)
    partition = list(partition) if partition else [(1, total)]
    if cfg.max_position_embeddings:
        max_len = min(max_len, cfg.max_position_embeddings)
    if stage_params is None:
        stage_params = [registry.module_shard_factory(
            model_name, model_file, l, r, stage=i, dtype=dtype,
            unroll=False)[1] for i, (l, r) in enumerate(partition)]
    family = registry.get_model_entry(model_name).family.FAMILY
    return DecodePipeline(family, cfg, partition, stage_params,
                          max_len=max_len, dtype=dtype,
                          cache_bits=cache_bits,
                          attend_floor=attend_floor, **pipe_kw)


class DecodePipeline:
    """Host-driven pipelined greedy decoding over block-aligned stages.

    `stage_params[i]` are forward-pipeline shard params (the same pytrees
    `module_shard_factory` builds); caches are per-stage. Decode steps are
    serial (autoregression), so batch is the throughput axis; stages
    partition the model across devices for capacity, exactly like the
    forward pipeline. `devices` optionally places each stage (device_put,
    mirroring the host pipeline driver).
    """

    def __init__(self, family, cfg: TransformerConfig,
                 partition: Sequence[Tuple[int, int]],
                 stage_params: Sequence[Dict], max_len: int,
                 devices: Optional[Sequence] = None, dtype=jnp.float32,
                 cache_bits: int = 0, mesh=None, tp_axis: str = "tp",
                 sp_mesh=None, sp_axis: str = "sp", sp_kind: str = "ring",
                 ep_mesh=None, ep_axis: str = "ep", tp_ep_mesh=None,
                 attend_floor: int = 64, int8_decode_attend=None):
        total = 4 * cfg.num_hidden_layers
        validate_partition(partition, total)
        validate_capacity(cfg, max_len)
        if mesh is not None and devices is not None:
            raise ValueError("pass either per-stage `devices` or a tp "
                             "`mesh`, not both")
        if sp_mesh is not None and (mesh is not None or cache_bits
                                    or devices is not None):
            raise ValueError("sp_mesh (sequence-parallel prefill) does not "
                             "compose with tp mesh/int8 cache/devices")
        if ep_mesh is not None and (mesh is not None or sp_mesh is not None
                                    or devices is not None):
            raise ValueError("ep_mesh (expert-parallel MoE decode) does not "
                             "compose with tp/sp meshes or devices")
        if tp_ep_mesh is not None and (mesh is not None or ep_mesh is not None
                                       or sp_mesh is not None or cache_bits
                                       or devices is not None):
            raise ValueError("tp_ep_mesh (tp x ep MoE decode) replaces the "
                             "single-axis meshes; it does not compose with "
                             "mesh/ep_mesh/sp_mesh, int8 cache, or devices")
        # what the family cannot do yet is refused here, by name
        if getattr(family, "cached_block_step", None) is not None:
            for asked, hook, what in (
                    (mesh, "tp_cached_block_step", "a tp mesh"),
                    (sp_mesh, "sp_prefill_block_step", "an sp_mesh"),
                    (ep_mesh, "ep_cached_block_step", "an ep_mesh"),
                    (tp_ep_mesh, "ep_cached_block_step", "a tp_ep_mesh")):
                if asked is not None and getattr(family, hook, None) is None:
                    raise NotImplementedError(
                        f"the {family.name} family has no {hook}: it does "
                        f"not decode under {what} yet")
        self.cache_leaves = None
        if getattr(family, "cache_leaves", None) is not None:
            if cache_bits:
                raise NotImplementedError(
                    f"the {family.name} family names its own cache leaves; "
                    "the int8 cache route covers the plain k, v cache only")
            self.cache_leaves = family.cache_leaves(cfg)
        # positions a prompt is prefilled at a time (None: one program for
        # the whole prompt)
        span = getattr(family, "prefill_span", None)
        self.prefill_span = span(cfg) if span is not None else None
        for name in ring_names(self.cache_leaves):
            ring = min(self.cache_leaves[name].length, max_len)
            if self.prefill_span and self.prefill_span > ring:
                raise ValueError(
                    f"the {family.name} family prefills in spans of "
                    f"{self.prefill_span} positions and its leaf {name!r} "
                    f"keeps a ring of {ring}: a span must fit the ring")
        self.family = family
        self.cfg = cfg
        self.max_len = max_len
        self.mesh, self.tp_axis = mesh, tp_axis
        self.tp_ep_mesh = tp_ep_mesh
        self.ep_mesh = ep_mesh
        # int8 decode-attend routing, resolved ONCE here (constructor
        # arg > env > QuantizeCompute promotion — `_resolve_int8_optin`)
        # and bound into the stage programs below; later env/config
        # toggles don't affect this pipeline (round-4 advice)
        optin = _resolve_int8_optin(int8_decode_attend)
        self.stages = []
        for i, (l, r) in enumerate(partition):
            sc = ShardConfig(l, r, is_first=l == 1, is_last=r == total)
            params = dict(stage_params[i])
            # restack an unrolled block layout ONCE here, not per traced call
            with telemetry.startup("weights_place"):
                params["blocks"] = stage_blocks(params)
            if tp_ep_mesh is not None:
                from jax.sharding import NamedSharding
                with telemetry.startup("programs"):
                    pre, dec, p_specs = make_tp_ep_stage_fns(
                        family, cfg, sc, tp_ep_mesh, params,
                        tp_axis=tp_axis, ep_axis=ep_axis)
                with telemetry.startup("weights_place"):
                    params = jax.tree_util.tree_map(
                        lambda x, s: jax.device_put(
                            x, NamedSharding(tp_ep_mesh, s)), params, p_specs)
                n_blocks = (r - l + 1) // 4
                self.stages.append({"prefill": pre, "decode": dec,
                                    "params": params, "n_blocks": n_blocks,
                                    "device": None})
                continue
            sharded = ((make_tp_stage_fns, mesh, tp_axis)
                       if mesh is not None else
                       (make_ep_stage_fns, ep_mesh, ep_axis)
                       if ep_mesh is not None else None)
            if sharded is not None:
                from jax.sharding import NamedSharding
                maker, m, ax = sharded
                kw = ({"cache_bits": cache_bits}
                      if maker in (make_tp_stage_fns, make_ep_stage_fns)
                      else {})
                with telemetry.startup("programs"):
                    pre, dec, p_specs = maker(family, cfg, sc, m, params,
                                              axis=ax, **kw)
                with telemetry.startup("weights_place"):
                    params = jax.tree_util.tree_map(
                        lambda x, s: jax.device_put(x, NamedSharding(m, s)),
                        params, p_specs)
            else:
                with telemetry.startup("programs"):
                    pre, dec = make_stage_fns(family, cfg, sc,
                                              int8_optin=optin)
                    if sp_mesh is not None:
                        pre = make_sp_prefill_fn(family, cfg, sc, sp_mesh,
                                                 axis=sp_axis,
                                                 sp_kind=sp_kind)
                if devices is not None:
                    with telemetry.startup("weights_place"):
                        params = jax.device_put(params, devices[i])
            n_blocks = (r - l + 1) // 4
            self.stages.append({"prefill": pre, "decode": dec,
                                "params": params, "n_blocks": n_blocks,
                                "runs": kind_runs(family, cfg, sc),
                                "device": None if devices is None or
                                mesh is not None else devices[i]})
        self.dtype = dtype
        self.cache_bits = cache_bits
        # the value bound into the stage programs above, exposed for
        # introspection
        self.int8_decode_optin = optin
        self.sp_degree = sp_mesh.shape[sp_axis] if sp_mesh is not None else 1
        # bucketed decode-step attention rides the plain stage programs
        # AND the tp variant (static read_len arg; the tp shard_map
        # closure re-binds per bucket); the ep/tp x ep variants attend
        # over the full window — their signatures don't take the bucket
        self._bucketed = ep_mesh is None and tp_ep_mesh is None
        if attend_floor < 1:
            raise ValueError(f"attend_floor must be >= 1, got {attend_floor}")
        self.attend_floor = attend_floor
        # the ladder `generate` asks `_read_len` for
        self.job_per_octave = job_per_octave(self.cache_leaves, self.stages)
        # the accounts of the last batches `generate` ran (plain dicts:
        # telemetry/generate_account.py), and the build counters a batch
        # reads to know whether a program was built inside it
        self.batch_accounts = deque(maxlen=generate_account.ACCOUNTS_KEPT)
        prom.count_jax_compiles()

    def _read_len(self, pos: int, span: int = 1, per_octave: int = 1):
        """Static attend window for a decode/span step whose last query
        row sits at host-known pos + span - 1 (None when this pipeline's
        stage programs aren't bucketed; ONE width, `max_len`, where no leaf
        keeps a row a position: `keeps_positions`), from `attend_bucket`'s
        ladder of `per_octave` widths an octave: 1 for every caller that
        must have met its programs before it serves, `job_per_octave` for
        `generate`, the batch job."""
        if not (self._bucketed and self.keeps_positions):
            return self.max_len if self._bucketed else None
        # a span's window is at least eight spans wide (a prompt prefilled
        # in spans of 512 starts at 4096, not at 512) and its widths are
        # whole spans apart; a step's are whole floors apart
        return attend_bucket(pos + span, self.max_len,
                             max(self.attend_floor, 8 * span if span > 1
                                 else 0), per_octave,
                             grain=max(self.attend_floor, span))

    def _fresh_caches(self, batch: int) -> List[Cache]:
        caches = []
        cache_mesh = self.mesh if self.mesh is not None else self.tp_ep_mesh
        for st in self.stages:
            c = init_cache(self.cfg, st["n_blocks"], batch, self.max_len,
                           self.dtype, cache_bits=self.cache_bits,
                           leaves=self.cache_leaves, runs=st.get("runs"))
            if cache_mesh is not None:
                from jax.sharding import NamedSharding
                # head axis over tp; replicated over ep when present
                specs = tp_cache_specs(c, self.tp_axis)
                c = {k: jax.device_put(v, NamedSharding(cache_mesh, specs[k]))
                     for k, v in c.items()}
            elif st["device"] is not None:
                c = jax.device_put(c, st["device"])
            caches.append(c)
        for name in caches[0] if self.cache_leaves else ():
            M_LEAF_BYTES.set(sum(c[name].nbytes for c in caches), leaf=name)
        return caches

    def _decode_step(self, st, data, cache, pos: int, span: int = 1,
                     last_only: bool = False, per_octave: int = 1):
        """Dispatch one stage's decode program at host-known `pos`,
        binding the static attend bucket when this pipeline is bucketed
        (the batcher dispatches through here too). `span` > 1 runs the
        same program shape over a K-token span [pos, pos+K) — the
        speculative-decoding verify step, and one span of a prompt
        prefilled in spans, whose head sees its last row only
        (`last_only`; the plain stage programs take it). `per_octave`
        names the bucket's ladder (`_read_len`). What the call attends is
        counted as it goes out (`M_ATTEND`): the window compiled for and
        the positions of it that are live, a row a query."""
        rl = self._read_len(pos, span, per_octave)
        queries = data.shape[0] * span * self.keeps_positions
        phase = "prefill" if span > 1 or last_only else "decode"
        M_ATTEND.inc(queries * (self.max_len if rl is None else rl),
                     phase=phase, kind="read")
        M_ATTEND.inc(queries * pos, phase=phase, kind="live")
        if rl is None:
            return st["decode"](st["params"], data, cache, pos)
        if last_only:
            return st["decode"](st["params"], data, cache, pos, read_len=rl,
                                last_only=True)
        return st["decode"](st["params"], data, cache, pos, read_len=rl)

    def _prefill(self, ids, prefill_ubatch: Optional[int] = None,
                 per_octave: int = 1, account=generate_account.NO_ACCOUNT):
        """Run the prompt through all stages; returns (last-stage output,
        per-stage caches). Where the family prefills in spans, each span
        attends a window off the ladder of `per_octave` (`_read_len`).
        `account` is `generate`'s of its batch: the `generate/alloc` and
        `generate/prefill` spans are its phases then, and each span's
        output a candidate mark.

        `prefill_ubatch` splits the batch into chunks so prefill PIPELINES
        across stages: JAX dispatch is asynchronous, so stage i's program
        runs on chunk c+1 while stage i+1 processes chunk c — the standard
        fill/drain overlap, with per-chunk caches concatenated on the batch
        axis afterwards. (For capacity-bounded MoE models chunking changes
        the routed token set, like any batch-size change.)"""
        batch = ids.shape[0]
        span = account.span

        def run_stages(data):
            with span("alloc"):
                caches = self._fresh_caches(data.shape[0])
            if self.prefill_span:       # span by span over the cache
                for start in range(0, data.shape[1], self.prefill_span):
                    with span("prefill"):
                        out, caches = self.extend(
                            data[:, start:start + self.prefill_span],
                            caches, start, last_only=True,
                            per_octave=per_octave)
                    account.span_out(out)
                return out, caches
            with span("prefill"):
                for i, st in enumerate(self.stages):
                    if st["device"] is not None:
                        data = jax.device_put(data, st["device"])
                    data, caches[i] = st["prefill"](st["params"], data,
                                                    caches[i])
            return data, caches

        if self.prefill_span:
            account.expect_spans(-(-ids.shape[1] // self.prefill_span))
        if prefill_ubatch is None or prefill_ubatch >= batch:
            return run_stages(ids)
        if prefill_ubatch <= 0:
            raise ValueError(f"prefill_ubatch must be positive, got "
                             f"{prefill_ubatch}")
        if batch % prefill_ubatch:
            raise ValueError(f"batch {batch} not divisible by "
                             f"prefill_ubatch {prefill_ubatch}")
        outs, chunk_caches = [], []
        for c0 in range(0, batch, prefill_ubatch):
            data, caches = run_stages(ids[c0:c0 + prefill_ubatch])
            outs.append(data)
            chunk_caches.append(caches)
        with span("prefill"):
            merged = [jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=1), *[cc[i] for cc in
                                                           chunk_caches])
                for i in range(len(self.stages))]
            return jnp.concatenate(outs, axis=0), merged

    def extend(self, tokens, caches, pos: int, last_only: bool = False,
               per_octave: int = 1):
        """Run a K-token span [B, K] through every stage at cache offset
        `pos`: K/V rows [pos, pos+K) are written and span row i attends
        cache positions [0, pos+i] (causal within the span, full history
        before it). Returns (last-stage output [B, K, ...], caches).

        This is the speculative-decoding VERIFY primitive: one pipelined
        forward scores K proposed tokens instead of K serial decode
        steps. K is static per call site (one compiled program per
        distinct span length x attend bucket; `per_octave` names the
        bucket's ladder, `_read_len`). With an int8 cache the
        in-span rows are attended unquantized (exactly like the current
        row of a plain decode step), so span scoring of K tokens is not
        bit-identical to K serial int8 steps — fp caches are exact."""
        tokens = jnp.asarray(tokens, jnp.int32)
        _, k = tokens.shape
        if pos + k > self.max_len:
            raise ValueError(f"span [{pos}, {pos + k}) exceeds max_len "
                             f"{self.max_len}")
        data = tokens
        for i, st in enumerate(self.stages):
            if st["device"] is not None:
                data = jax.device_put(data, st["device"])
            data, caches[i] = self._decode_step(
                st, data, caches[i], pos, span=k,
                last_only=last_only and i == len(self.stages) - 1,
                per_octave=per_octave)
        return data, caches

    def precompute_prefix(self, prefix_ids) -> Dict:
        """Prefill a shared prompt PREFIX once, for reuse across requests
        (prompt caching): returns an opaque handle for `generate(...,
        prefix=)`. `prefix_ids` is [P] or [1, P]; the cached K/V rows are
        broadcast to each request batch at use. Exact for fp caches
        (suffix tokens attend prefix K/V exactly as a monolithic prefill
        would); with int8 caches the monolithic prefill attends its own
        prompt rows unquantized, so prefix reuse introduces the cached
        rows' quantization error — same caveat class as chunked
        prefill's routing note."""
        ids = jnp.asarray(prefix_ids, jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        if ids.shape[0] != 1:
            raise ValueError("a shared prefix is one sequence; got batch "
                             f"{ids.shape[0]}")
        if ids.shape[1] % self.sp_degree:
            raise ValueError(f"prefix length {ids.shape[1]} not divisible "
                             f"by the sp prefill degree {self.sp_degree}")
        _, caches = self._prefill(ids)
        return {"caches": caches, "len": ids.shape[1],
                "sig": self._prefix_sig()}

    def _prefix_sig(self) -> Tuple:
        """Cache-compatibility signature stamped into prefix handles: a
        handle built by one pipeline is only valid on a pipeline whose
        per-stage cache layout (block split, max_len, quantization,
        dtype, KV geometry, and the geometry of every leaf the family names:
        its shape and type, the kind of block that owns it and whether it is
        a row a position, a ring of them, a row every few or a row a request)
        matches — a mismatched handle would otherwise die deep inside jit
        with an opaque shape error or silently corrupt attend windows
        (round-4 advice)."""
        named = tuple(
            (name, tuple(leaf.shape), jnp.dtype(leaf.dtype).name,
             getattr(leaf, "kind", None), getattr(leaf, "whole", False))
            + ((leaf.length,) if getattr(leaf, "length", 0) else ())
            + ((leaf.stride, leaf.reach) if getattr(leaf, "stride", 0)
               else ())
            for name, leaf in sorted((self.cache_leaves or {}).items()))
        return ("decode-prefix-v2",
                tuple(st.get("runs") or st["n_blocks"]
                      for st in self.stages),
                self.max_len, self.cache_bits,
                jax.dtypes.canonicalize_dtype(self.dtype).name,
                self.cfg.kv_heads, self.cfg.head_dim, named)

    def check_prefix(self, prefix: Dict) -> None:
        """Validate a `precompute_prefix` handle against THIS pipeline's
        cache layout (see `_prefix_sig`); raises ValueError with the two
        signatures on mismatch."""
        sig = prefix.get("sig") if isinstance(prefix, dict) else None
        if sig is None:
            raise ValueError(
                "prefix is not a precompute_prefix handle (no 'sig' "
                "stamp); build it with this pipeline's precompute_prefix")
        if sig != self._prefix_sig():
            raise ValueError(
                "prefix handle was built by an incompatible pipeline: "
                f"handle sig {sig} vs this pipeline {self._prefix_sig()} "
                "(fields: version, per-stage runs of blocks, max_len, "
                "cache_bits, dtype, kv_heads, head_dim, named leaves)")

    def generate(self, ids, new_tokens: int, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0, step_callback=None,
                 prefill_ubatch: Optional[int] = None,
                 prefix: Optional[Dict] = None):
        """Decode `new_tokens` continuations of prompt `ids` [B, S].

        `temperature=0` (default) is greedy argmax; otherwise tokens are
        sampled from logits/temperature, optionally truncated to the
        `top_k` most likely. `step_callback(step, tokens)` fires after each
        decode step (e.g. for monitoring heartbeats). `prefill_ubatch`
        pipelines the prompt pass across stages in batch chunks (see
        `_prefill`). `prefix` (from `precompute_prefix`) seeds the caches
        with a shared prompt prefix; `ids` is then each request's SUFFIX,
        run as one span at the prefix offset instead of a fresh prefill.
        Returns [B, S + new_tokens] token ids (the prefix is not
        included in the returned array).

        This is the batch job: it meets every attend width of its schedule
        in its first batch, so its steps, its prompt's spans and its
        prefix's suffix ask for the fine ladder (`job_per_octave`,
        `attend_bucket`) and attend a window close to the live length. The
        tokens are those of any other ladder: the positions a wider window
        adds are masked to exact zeros."""
        if new_tokens <= 0:
            return jnp.asarray(ids, jnp.int32)
        rows, suffix_len = jnp.shape(ids)
        with generate_account.BatchAccount(self.batch_accounts, rows,
                                           suffix_len, new_tokens) as account:
            return self._generate(account, ids, new_tokens, temperature,
                                  top_k, seed, step_callback, prefill_ubatch,
                                  prefix)

    def _generate(self, account, ids, new_tokens, temperature, top_k, seed,
                  step_callback, prefill_ubatch, prefix):
        """`generate`'s batch, inside its `generate/batch` span: every call
        into the runtime is a phase of `account`
        (`telemetry/generate_account.py`), which watches the device's
        progress on the tokens picked and fences nothing before the last
        dispatch."""
        ids = jnp.asarray(ids, jnp.int32)
        batch, suffix_len = ids.shape
        prompt_len = suffix_len + (prefix["len"] if prefix else 0)
        validate_capacity(self.cfg, self.max_len, prompt_len, new_tokens)
        if prefix is None and prompt_len % self.sp_degree:
            raise ValueError(f"prompt length {prompt_len} not divisible by "
                             f"the sp prefill degree {self.sp_degree}")
        rng = jax.random.PRNGKey(seed)
        pick = make_next_picker(temperature, top_k)

        if prefix is not None:
            self.check_prefix(prefix)
            if prefill_ubatch is not None:
                raise ValueError("prefix reuse runs the suffix as one "
                                 "span; --prefill-ubatch does not apply")
            if suffix_len == 0:
                raise ValueError(
                    "prefix reuse needs a non-empty suffix (the span "
                    "produces the first token's logits); keep at least "
                    "the last prompt token out of the prefix")
            # broadcast the prefix's B=1 cache rows to this batch (the
            # beam-search batch-tiling rule), then run the whole suffix
            # as one span at the prefix offset
            with account.span("alloc"):
                caches = [_repeat_batch(c, batch) for c in prefix["caches"]]
            with account.span("prefill"):
                data, caches = self.extend(ids, caches, prefix["len"],
                                           per_octave=self.job_per_octave)
        else:
            data, caches = self._prefill(ids, prefill_ubatch,
                                         per_octave=self.job_per_octave,
                                         account=account)
        # the counts as the prompt left them: copies, since the caches are
        # donated to the steps; read back once, after the last step
        with account.span("finish"):
            after_prompt = [c[STATS] + 0 for c in caches if STATS in c]
        tokens = []
        for step in range(new_tokens):
            if step:
                with account.span("step"):
                    for i, st in enumerate(self.stages):
                        if st["device"] is not None:
                            data = jax.device_put(data, st["device"])
                        data, caches[i] = self._decode_step(
                            st, data, caches[i], prompt_len + step - 1,
                            per_octave=self.job_per_octave)
            with account.span("pick"):
                token, data, rng = pick(data, rng)
            tokens.append(token)
            account.token(token)
            if step_callback is not None:
                step_callback(step, token)
        # one program, dispatched before the wait, so the device never
        # waits for the host
        with account.span("finish"):
            result = join_tokens(ids, *tokens)
        account.wait()
        if after_prompt:
            with account.span("finish"):
                self._count(after_prompt, caches)
        return result

    def _count(self, after_prompt, caches) -> None:
        """Add a batch's device counts to the registry's counters, by
        phase: what the prompt counted, and what the steps added."""
        prompt = sum(read_stats({STATS: s}) for s in after_prompt)
        total = sum(read_stats(c) for c in caches if STATS in c)
        count_stats(self.family.stats_names, prompt, total - prompt)

    def generate_beam(self, ids, new_tokens: int, beams: int):
        """Beam-search decode: keep the `beams` highest log-probability
        continuations per prompt, return the best [B, S + new_tokens].

        Beams fold into the batch axis (row i*beams + b), so the compiled
        stage programs are reused unchanged at batch B*beams; on each
        reshuffle the per-stage caches are reordered along that axis to
        follow their surviving parent beams. Pure max-log-prob beam search:
        fixed horizon, no EOS/length normalization (all hypotheses share a
        length), matching the exhaustive oracle in tests/test_decode.py."""
        ids = jnp.asarray(ids, jnp.int32)
        batch, prompt_len = ids.shape
        if new_tokens <= 0:
            return ids
        if beams < 1:
            raise ValueError(f"beams must be >= 1, got {beams}")
        if beams == 1:
            # a width-1 beam IS greedy; skip the per-step cache gather
            return self.generate(ids, new_tokens)
        validate_capacity(self.cfg, self.max_len, prompt_len, new_tokens)
        if prompt_len % self.sp_degree:
            raise ValueError(f"prompt length {prompt_len} not divisible by "
                             f"the sp prefill degree {self.sp_degree}")

        # prefill once at batch B, then tile each prompt's cache per beam
        data, caches = self._prefill(ids)
        caches = [_repeat_batch(c, beams) for c in caches]

        logp = jax.nn.log_softmax(
            data[:, -1].astype(jnp.float32), axis=-1)     # [B, V]
        scores, first = jax.lax.top_k(logp, beams)        # [B, beams]
        history = first[..., None]                        # [B, beams, 1]

        for step in range(1, new_tokens):
            pos = prompt_len + step - 1
            data = history[:, :, -1].reshape(batch * beams, 1)
            for i, st in enumerate(self.stages):
                if st["device"] is not None:
                    data = jax.device_put(data, st["device"])
                data, caches[i] = self._decode_step(st, data, caches[i],
                                                    pos)
            logp = jax.nn.log_softmax(
                data[:, 0].astype(jnp.float32), axis=-1)  # [B*beams, V]
            vocab = logp.shape[-1]
            total = scores[..., None] + logp.reshape(batch, beams, vocab)
            scores, flat = jax.lax.top_k(total.reshape(batch, -1), beams)
            parent = flat // vocab                        # [B, beams]
            token = flat % vocab
            rows = (jnp.arange(batch)[:, None] * beams + parent).reshape(-1)
            caches = [_gather_batch(c, rows) for c in caches]
            history = jnp.concatenate(
                [jnp.take_along_axis(history, parent[..., None], axis=1),
                 token[..., None]], axis=2)

        best = jnp.argmax(scores, axis=1)
        best_hist = jnp.take_along_axis(
            history, best[:, None, None], axis=1)[:, 0]   # [B, new_tokens]
        return jnp.concatenate([ids, best_hist], axis=1)

    @cached_property
    def keeps_positions(self) -> bool:
        """Whether any leaf of the stages' caches is a row a position (the
        plain `k`, `v` pair; a family's leaf that is not `whole`; a ring,
        which is read whatever the window). Where none is (a family whose
        every leaf is a recurrent state a request: models/brumby.py) there
        is no window to bucket: `_read_len` binds the one width `max_len`
        into every span and step program, so a generation builds ONE of
        each whatever its positions (an octave's widths would be programs
        that differ in a number nothing reads), `max_len` bounds the
        positions a rotation may see and nothing in memory, and
        `_decode_step` counts no attended position (`M_ATTEND`). Below the
        last line that stands in a Mosaic kernel's call stack, so that the
        siblings' step programs keep their keys in the compile cache
        (ROADMAP S9)."""
        leaves = self.cache_leaves
        return leaves is None \
            or bool(set(leaves) - {STATS} - set(whole_names(leaves)))
