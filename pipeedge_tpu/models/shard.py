"""Generic shard execution engine shared by all model families.

The reference runs a Python list of per-block torch sub-modules
(vit.py:161-170, bert.py:142-151, deit.py:157-166). Here a shard executes as:

    embeddings? -> partial head block -> lax.scan over stacked full blocks
                -> partial tail block -> final norm/pooler/classifier?

One compiled block body serves any pipeline depth (compile time independent of
layer count), parameters for the scanned blocks live as one stacked pytree
(leading axis = block), and partial blocks at the shard edges — which exist
because PipeEdge partitions at sublayer granularity — are unrolled explicitly.

A model family plugs in three pure functions via `FamilySpec`:
  embed(embed_params, raw_input, cfg)        -> hidden [B, S, D]
  sublayer(block_params, sub, payload, cfg)  -> payload (tensor or 2-tuple)
  finalize(final_params, hidden, cfg)        -> model output
"""
from __future__ import annotations

import dataclasses
from functools import partial
from itertools import groupby
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from . import BlockSlice, ShardConfig, plan_shard
from .layers import TransformerConfig

ShardData = Any  # jax.Array | tuple[jax.Array, jax.Array]


class BlockRuns(NamedTuple):
    """A shard's full blocks where they are not all of one kind (a leading
    dense layer before the expert layers): one stacked pytree `[n, ...]` a
    run of like blocks, in the model's order. The decode scan takes a run
    at a time (the decode driver's `_run_blocks`); a shard of one kind keeps
    the bare stacked pytree."""
    runs: tuple


class CacheLeaf(NamedTuple):
    """One leaf of a stage's cache, where a family says more of it than what
    follows `[L, B, T]` (a bare `jax.ShapeDtypeStruct` says that much).
    `kind`: the kind of block (`FamilySpec.block_kind`) that owns the leaf,
    or a tuple of kinds where blocks of more than one own it (a mixer's leaf
    in a model whose blocks also differ by their FFN: the runs differ, the
    leaf is one); their count in the stage is the leaf's `L`, in the model's
    order across those kinds; None = every block. `whole`: the leaf is a row
    a request, `[L, B] + shape`, replaced whole by every call (a recurrent
    state), and not a row a position, `[L, B, T] + shape`, written at
    `pos`. `length`: the positions the leaf keeps where that is not the
    stage's `max_len` (0): a RING of the last `length` positions, `[L, B,
    min(length, max_len)] + shape`, position `p` at slot `p mod length`
    (a layer that attends a window of that many positions and needs keep no
    more; models/stage_cache.py, "A ring"). `stride`: the leaf keeps a row
    every `stride` positions, `[L, B, max_len // stride] + shape`, row `j` a
    summary of the `reach` positions from `stride * j` (pooled keys), written
    by the call that brings the last of them (models/stage_cache.py, "A
    stride")."""
    shape: tuple
    dtype: Any
    kind: Any = None
    whole: bool = False
    length: int = 0
    stride: int = 0
    reach: int = 0


def kind_runs(family, cfg: TransformerConfig,
              shard_config: ShardConfig) -> tuple:
    """The shard's full blocks as runs of like blocks, in the model's order:
    `((kind, count), ...)`, one entry where all are alike (`kind` None where
    the family tells no kinds apart). The runs `build_shard_params` stacks."""
    kind = getattr(family, "block_kind", None)
    return tuple((k, len(list(run))) for k, run in groupby(
        plan_shard(shard_config).full_ids,
        key=(lambda b: kind(cfg, b)) if kind else (lambda b: None)))


@dataclasses.dataclass(frozen=True)
class FamilySpec:
    """Pure-function hooks defining a model family (vit/bert/deit/gpt2/
    llama). The two optional hooks plug a decoder family into the
    KV-cache decode subsystem (the decode drivers): `cached_block_step`
    replaces the default GPT-2-shaped block step, `decode_embed` the
    default wte+wpe single-token embedding."""
    name: str
    embed: Callable[[Dict, Any, TransformerConfig], jax.Array]
    sublayer: Callable[[Dict, int, ShardData, TransformerConfig], ShardData]
    finalize: Callable[[Dict, jax.Array, TransformerConfig], jax.Array]
    cached_block_step: Any = None    # (p, x, bcache, pos, cfg, prefill)
    decode_embed: Any = None         # (embed_params, tok, pos) -> [B, 1, D]
    span_embed: Any = None           # (embed_params, tok [B,K], pos) ->
    #                                  [B, K, D] (speculative verify span)
    # a causal decoder: what the decoding CLIs take (`registry.decoder_model`)
    decoder_model: bool = False
    # attention reads absolute positions (RoPE): chunk-local attention
    # overrides (sequence-parallel cores) would rotate at wrong offsets
    position_dependent_attention: bool = False
    # tensor-parallel variants of the decode step (per-device bodies under shard_map;
    # families whose cached step differs from the GPT-2 shape supply them)
    tp_cached_block_step: Any = None  # (+ axis=...) kwarg
    tp_finalize: Any = None           # (pf, hidden, cfg, axis) vocab-sharded
    # sequence-parallel prefill block for position-dependent families:
    # (p, x, bcache, cfg, axis, core, cache_gather) -> (x, bcache)
    sp_prefill_block_step: Any = None
    # a family whose cache is not the plain `k`, `v` pair names its leaves:
    # (cfg) -> {name: what follows [L, B, T], or a `CacheLeaf`} (`init_cache`)
    cache_leaves: Any = None
    # (cfg) -> positions a prompt is prefilled at a time, through the
    # decode-shaped stage program; None = one whole-prompt prefill program
    prefill_span: Any = None
    # block leaves the decode scan does not slice a layer at a time but
    # hands the block step whole, as `(stack, layer)`
    whole_leaves: tuple = ()
    # what the block steps count into the cache's `stats` leaf, in order:
    # each is read back once a batch into `pipeedge_<name>_total{phase}`
    stats_names: tuple = ()
    # (cfg, block_id) -> the kind of that block, where a model's blocks
    # differ in their leaves: consecutive blocks of one kind are one run
    # (`build_shard_params`' `kind`, `BlockRuns`); None = all alike
    block_kind: Any = None
    # sublayers that LEAD with a dense and accept an 8-bit wire
    # `QuantizedTensor` as the payload's first tensor (the int8
    # stage-seam tunnel, parallel/pipeline.py + ops/int8_matmul.py)
    wire_subs: tuple = ()
    # `cached_block_step` for one token a row, each row at its own position:
    # (p, x, bcache, at: stage_cache.RowsAt, cfg, block) -> (x, bcache), around
    # `stage_cache.attend_rows` (parallel/decode_rows.py: the served
    # executor steps all running rows in one program where a family has it)
    rows_block_step: Any = None


def _apply_slice(family: FamilySpec, block_params: Dict, data: ShardData,
                 blk: BlockSlice, cfg: TransformerConfig) -> ShardData:
    for sub in blk.sublayers():
        data = family.sublayer(block_params, sub, data, cfg)
    return data


def shard_apply(family: FamilySpec, cfg: TransformerConfig,
                shard_config: ShardConfig, params: Dict,
                data: ShardData) -> ShardData:
    """Apply one layer-range shard. Pure; jit with cfg/shard_config static.

    The full blocks run in one of two layouts, detected from the params:

    - stacked pytree [n_blocks, ...] -> `lax.scan` (compile time independent
      of depth; also the layout the SPMD driver takes as its stage-sharded
      input, which it takes apart once a call and runs unrolled too:
      parallel/spmd.py::run_blocks);
    - tuple of per-block pytrees (see `unstack_blocks`) -> unrolled loop.
      The scan's loop-carried dynamic-slice of the stacked weights is real
      HBM traffic each iteration (25 MB a ViT-Large block), while unrolled
      blocks read their own arrays directly. Measured on the v5e (PR 30,
      `vit-l.spmd-4stage`, six ViT-Large blocks a tick at microbatch 8 in
      bfloat16): 2.360 ms a tick as a scan over the stack, 2.083 ms
      unrolled, 3,388 -> 3,833 img/s on four chips. A static slice of the
      stacked layout inside the loop does NOT recover this (XLA materializes
      the slices every iteration); one made outside the loop, once a call,
      does. The unrolled body compiles six blocks where the scan compiles
      one (13.6 s against 6.6 s in a first run of that program).
    """
    plan = plan_shard(shard_config)
    if shard_config.is_first:
        data = family.embed(params["embeddings"], data, cfg)
    if plan.head is not None:
        data = _apply_slice(family, params["head"], data, plan.head, cfg)
    if plan.full_ids:
        full = BlockSlice(0, 0, 3)
        blocks = params["blocks"]
        if isinstance(blocks, BlockRuns):
            raise NotImplementedError(
                f"the {family.name} family's blocks come in runs of "
                "different kinds, which the forward path does not scan yet; "
                "it runs through the cached decode path (`DecodePipeline`)")
        if isinstance(blocks, (tuple, list)):
            for block_params in blocks:
                data = _apply_slice(family, block_params, data, full, cfg)
        else:
            def body(carry, block_params):
                return _apply_slice(family, block_params, carry, full, cfg), None

            data, _ = jax.lax.scan(body, data, blocks)
    if plan.tail is not None:
        data = _apply_slice(family, params["tail"], data, plan.tail, cfg)
    if shard_config.is_last:
        data = family.finalize(params["final"], data, cfg)
    return data


def make_shard_fn(family: FamilySpec, cfg: TransformerConfig,
                  shard_config: ShardConfig) -> Callable[[Dict, ShardData], ShardData]:
    """Return a jit-compiled `fn(params, data)` for this shard signature."""
    return jax.jit(partial(shard_apply, family, cfg, shard_config))


def stack_blocks(block_param_list):
    """Stack per-block parameter pytrees into one scanned pytree [L, ...]."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *block_param_list)


def unstack_blocks(params: Dict) -> Dict:
    """Convert a shard's stacked 'blocks' pytree to a tuple of per-block
    pytrees, selecting the unrolled execution path in `shard_apply` (see its
    docstring for the measured TPU win). No-op for shards without full
    blocks or already-unstacked params."""
    blocks = params.get("blocks")
    if blocks is None or isinstance(blocks, (tuple, list)):     # or runs
        return params
    n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    out = dict(params)
    out["blocks"] = tuple(
        jax.tree_util.tree_map(lambda x, i=i: x[i], blocks) for i in range(n))
    return out


def build_shard_params(shard_config: ShardConfig,
                       get_embed: Callable[[], Dict],
                       get_block: Callable[[int, tuple], Dict],
                       get_final: Callable[[], Dict],
                       stack: Callable = None,
                       kind: Callable = None) -> Dict:
    """Assemble a shard's parameter pytree from per-component getters.

    `get_block(block_id, sublayers)` returns only the parameters the listed
    sublayers need — a shard never materializes weights outside its layer
    range, mirroring the reference's lazy npz slicing (vit.py:93-118).
    `stack` replaces `stack_blocks` (a family whose getters return host
    arrays stacks them there). `kind(block_id)` tells blocks of different
    leaves apart: each run of consecutive like blocks is stacked on its
    own, and where there is more than one the shard's blocks are a
    `BlockRuns`.
    """
    plan = plan_shard(shard_config)
    params: Dict = {}
    if shard_config.is_first:
        params["embeddings"] = get_embed()
    if plan.head is not None:
        params["head"] = get_block(plan.head.block_id, tuple(plan.head.sublayers()))
    if plan.full_ids:
        runs = [(stack or stack_blocks)(
            [get_block(b, (0, 1, 2, 3)) for b in run])
            for _, run in groupby(plan.full_ids,
                                  key=kind or (lambda b: None))]
        params["blocks"] = runs[0] if len(runs) == 1 \
            else BlockRuns(tuple(runs))
    if plan.tail is not None:
        params["tail"] = get_block(plan.tail.block_id, tuple(plan.tail.sublayers()))
    if shard_config.is_last:
        params["final"] = get_final()
    return params
