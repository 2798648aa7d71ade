"""SPMD pipeline: the whole stage graph as ONE jitted program over a mesh.

The performance path (SURVEY.md §5.8, §7 step 3b). Where the host-driven
driver dispatches per-stage programs with device_put edges, this compiles the
*entire* pipeline — all stages, all microbatches — into a single XLA program
under `shard_map` over a `jax.sharding.Mesh`:

- mesh axes ('dp', 'stage'): 'stage' is the pipeline axis (the reference's
  rank, comm/p2p), 'dp' optionally shards the microbatch dimension (data
  parallelism within a stage — absent in the reference, SURVEY.md §2.4).
- Each device holds only its own stage's transformer blocks (parameters are
  stage-sharded; stages with fewer blocks are zero-padded and masked). A tick
  runs them unrolled over per-block arrays sliced out of the stage's stack
  once a call; only the padded slots sit under a `lax.cond`.
- One `lax.scan` over T "ticks" runs the fill/steady/drain schedule; the
  inter-stage edge is `lax.ppermute` over ICI — the collective-permute
  equivalent of the reference's gloo send/recv threads (p2p:155-258), with
  zero host involvement in steady state.
- The edge leaves a tick ahead of its use where the round is long enough
  (`edge_lead`): a tick sends what the stage finished in the last tick and
  computes on what landed in the last tick, so no block of a tick waits for
  that tick's transfer and the compiler runs the stage's blocks between
  `collective-permute-start` and `-done`. Stage s then works on microbatch
  t - 2s and T = n_microbatches + 2 (n_stages - 1). A short round, or one
  stage, keeps the transfer at the head of the tick: stage s on microbatch
  t - s, T = n_microbatches + n_stages - 1 (`SpmdPipeline.n_ticks`).
- Quantized edges: the payload is encoded to packed uint32 before the
  ppermute and decoded after, so only 32/bit of the activation bytes cross
  the interconnect (QuantPipe on the wire, reference runtime.py:73-119).

Constraints vs the host-driven path: partitions must be block-aligned (each
stage = whole transformer blocks). Mid-block (sublayer) cuts stream a 2-tuple
payload with shapes that differ per cut point, which would break the single
SPMD program; the host-driven driver handles those (SURVEY.md §7 hard parts).
"""
from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry
from ..utils import jax_compat
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import block_slices
from ..models.layers import TransformerConfig
from ..models.shard import FamilySpec, stack_blocks
from ..ops import fused_quant
from ..ops import quant as quant_ops
from ..telemetry import metrics as prom

logger = logging.getLogger(__name__)

# /metrics plane: whether a deployment's partition engages the unrolled
# block body. Set at build from the partition alone: `unconditional` slots
# run with no `lax.cond` in the tick, `masked` ones are some stage's padding
_M_STAGE_BLOCKS = prom.REGISTRY.gauge(
    "pipeedge_spmd_stage_blocks",
    "block slots a tick of the newest SPMD pipeline runs, by kind: "
    "unconditional (every stage holds the block) / masked (padding on the "
    "shallower stages, under lax.cond)")

_M_EDGE_LEAD = prom.REGISTRY.gauge(
    "pipeedge_spmd_edge_lead_ticks",
    "ticks by which the newest SPMD program's edge leaves ahead of its use: "
    "1 = the transfer rides behind the stage's blocks, 0 = the tick starts "
    "with it (one stage, or a round too short to pay the added ticks)")
_M_TICKS = prom.REGISTRY.gauge(
    "pipeedge_spmd_ticks",
    "ticks the newest SPMD program's scan runs a call: microbatches + "
    "(1 + lead) x (stages - 1)")

# The lead is taken where the ticks it adds are a smaller share of a round,
# (n_stages - 1) / n_ticks, than what it takes off a tick. On ViT-L's six
# blocks a stage and a bf16[8,197,1024] payload over four v5e chips a tick
# fell 2.6% at 128 microbatches and 2.2% at 1,024, and a round was 0.3%
# slower with the lead at 96 and 0.4% faster at 128 (PERF.md section 6,
# PR 42: `tools/bench_spmd_lead.py`): four stages break even near 114
EDGE_LEAD_SHARE = 0.025

BlockRange = Tuple[int, int]


def edge_lead(n_ubatch: int, n_stages: int) -> int:
    """Ticks by which a stage's output leaves ahead of its use (0 or 1),
    from the call's shapes alone."""
    added = n_stages - 1    # ticks a round; one stage has no edge to lead
    return int(0 < added < EDGE_LEAD_SHARE * (n_ubatch + 2 * added))


def partition_to_blocks(partition: Sequence[Tuple[int, int]]) -> List[BlockRange]:
    """Convert a sublayer partition to 0-based block ranges; reject mid-block cuts."""
    out = []
    for layer_start, layer_end in partition:
        slices = block_slices(layer_start, layer_end)
        if not all(s.is_full for s in slices):
            raise ValueError(
                f"SPMD pipeline requires block-aligned partitions; "
                f"[{layer_start}, {layer_end}] cuts mid-block (use the "
                f"host-driven pipeline for sublayer cuts)")
        out.append((slices[0].block_id, slices[-1].block_id))
    return out


def _pad_stack(stage_blocks: List[Any], max_b: int):
    """Stack per-stage block pytrees [n_i, ...] into [n_stages, max_b, ...]."""
    def pad(leaf):
        pad_width = [(0, max_b - leaf.shape[0])] + [(0, 0)] * (leaf.ndim - 1)
        return jnp.pad(leaf, pad_width)

    padded = [jax.tree_util.tree_map(pad, b) for b in stage_blocks]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


def _raw_words(n_values: int, itemsize: int) -> int:
    """uint32 words to carry `n_values` raw elements of `itemsize` bytes."""
    return -(-n_values * itemsize // 4)


def _bitcast_to_words(h: jax.Array) -> jax.Array:
    """[B, ...] -> [B, words] uint32 view of the raw payload (bit=0 edges in
    a mixed-bitwidth wire format)."""
    b = h.shape[0]
    flat = h.reshape(b, -1)
    if h.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if h.dtype == jnp.bfloat16:
        u16 = jax.lax.bitcast_convert_type(flat, jnp.uint16)
        return jax.lax.bitcast_convert_type(u16.reshape(b, -1, 2), jnp.uint32)
    raise TypeError(f"unsupported raw edge dtype {h.dtype}")


def _bitcast_from_words(words: jax.Array, shape, dtype) -> jax.Array:
    """Inverse of `_bitcast_to_words` for the leading [B, words] block."""
    b = shape[0]
    n = int(np.prod(shape[1:]))
    if dtype == jnp.float32:
        flat = jax.lax.bitcast_convert_type(words[:, :n], jnp.float32)
    elif dtype == jnp.bfloat16:
        u16 = jax.lax.bitcast_convert_type(words[:, :n // 2], jnp.uint16)
        flat = jax.lax.bitcast_convert_type(u16, jnp.bfloat16).reshape(b, -1)
    else:
        raise TypeError(f"unsupported raw edge dtype {dtype}")
    return flat.reshape(shape)


def _stacked_block_specs(cfg, blocks_tree, tp: int):
    """Partition specs for the stacked block params [n_stages, max_b, ...]:
    stage-sharded on the leading axis, and — when the mesh has a 'tp' axis —
    Megatron column/row sharded on the kernel dims per the SAME family spec
    tables the TP block bodies compile against (parallel/tensor.py)."""
    if tp <= 1:
        return jax.tree_util.tree_map(lambda _: P("stage"), blocks_tree)
    from .tensor import family_tp_plan
    table, _ = family_tp_plan(cfg)
    return jax.tree_util.tree_map(
        lambda _, s: P(*(("stage", None) + tuple(s))), blocks_tree, table)


@dataclasses.dataclass
class SpmdPipeline:
    """Compiled SPMD pipeline over a ('dp', 'stage') mesh.

    Build with `build_spmd_pipeline`. Call `run(inputs)` with a stacked
    microbatch array [M, B, ...raw input dims...]; returns [M, B, ...out...].

    `stage_bits[i]` quantizes the edge leaving stage i (reference `-q`
    per-stage semantics, runtime.py:652-656). Uniform bits compile to the
    direct QuantizedTensor edge; mixed bits compile to a `lax.switch` over
    per-bitwidth encoders writing one uniform padded uint32 wire buffer —
    shapes must be identical across devices in an SPMD program, so the
    buffer is sized for the widest edge and each stage's branch zero-pads.
    """
    family: FamilySpec
    cfg: TransformerConfig
    mesh: Mesh
    n_stages: int
    max_blocks: int         # the deepest stage's block count (slots a tick)
    min_blocks: int         # the shallowest stage's: slots with no padding
    params: Dict            # {'embed', 'final', 'blocks', 'n_blocks'}
    stage_bits: Tuple[int, ...] = (0,)
    sp_kind: str = "ring"   # sp attention core: 'ring' | 'ulysses'
    remat: bool = False     # checkpoint each block (training memory)
    _compiled: Dict[Tuple, Any] = dataclasses.field(default_factory=dict)

    @property
    def quant_bit(self) -> int:
        """Uniform edge bitwidth (0 when edges are mixed) — legacy accessor."""
        bits = set(self.stage_bits[:-1] or (0,))
        return next(iter(bits)) if len(bits) == 1 else 0

    def compiled_for(self, inputs: jax.Array):
        """The param-explicit compiled program `fn(params, inputs)` for
        this input shape (cached per shape/dtype/edge-bits) — the public
        handle `run()`, the training step, and tests share."""
        from .tensor import get_tp_quant_bits
        # the intra-stage collective bitwidth is a trace-time flag
        # (tensor.set_tp_quant_bits): keying the cache on it makes a
        # flag flip rebuild instead of silently reusing the stale trace
        key = (inputs.shape, str(inputs.dtype), self.stage_bits,
               get_tp_quant_bits(), edge_lead(inputs.shape[0], self.n_stages))
        fn = self._compiled.get(key)
        if fn is None:
            # the program waits for its input's shape: built at the first
            # call, not in `build_spmd_pipeline`
            with telemetry.startup("programs"):
                fn = self._build(inputs)
            self._compiled[key] = fn
        return fn

    def n_ticks(self, n_ubatch: int) -> int:
        """Trips of the tick scan a call of `n_ubatch` microbatches makes:
        stage s works on microbatch t - (1 + lead) s."""
        lead = edge_lead(n_ubatch, self.n_stages)
        return n_ubatch + (1 + lead) * (self.n_stages - 1)

    def run(self, inputs: jax.Array) -> jax.Array:
        fn = self.compiled_for(inputs)
        dp_spec = "dp" if self.mesh.shape.get("dp", 1) > 1 else None
        inputs = jax.device_put(inputs, NamedSharding(self.mesh, P(None, dp_spec)))
        return fn(self.params, inputs)

    # -- program construction ------------------------------------------

    def _build(self, inputs: jax.Array):
        family, cfg = self.family, self.cfg
        n_stages, max_b = self.n_stages, self.max_blocks
        min_b = self.min_blocks
        mesh = self.mesh
        n_ubatch = inputs.shape[0]
        lead = edge_lead(n_ubatch, n_stages)
        n_ticks = self.n_ticks(n_ubatch)
        _M_EDGE_LEAD.set(lead)
        _M_TICKS.set(n_ticks)
        dp = mesh.shape.get("dp", 1)

        sp = mesh.shape.get("sp", 1)
        # intra-stage collective bitwidth, pinned for THIS trace (the
        # compile cache key carries it, so a later flag flip retraces)
        from .tensor import get_tp_quant_bits
        collective_bits = get_tp_quant_bits()

        # trace shapes: embedded hidden + final output
        embed_shape = jax.eval_shape(
            partial(family.embed, cfg=cfg), self.params["embed"], inputs[0])
        b_local = embed_shape.shape[0] // dp
        seq_total = embed_shape.shape[1]
        if seq_total % sp:
            raise ValueError(f"sequence length {seq_total} must divide by "
                             f"the sp mesh axis ({sp})")
        s_local = seq_total // sp
        # per-device hidden: sequence-sharded over 'sp' (stage edges then
        # carry only the local chunk — sequence-parallel pipeline comm)
        hidden_local = jax.ShapeDtypeStruct(
            (b_local, s_local) + embed_shape.shape[2:], embed_shape.dtype)
        # finalize consumes the FULL sequence (CLS token / pooler): under sp
        # the last stage all-gathers the chunks first
        out_shape = jax.eval_shape(
            partial(family.finalize, cfg=cfg), self.params["final"],
            jnp.zeros((b_local, seq_total) + embed_shape.shape[2:],
                      embed_shape.dtype))

        tp = mesh.shape.get("tp", 1)
        if tp > 1 and sp > 1:
            raise ValueError("tp and sp mesh axes are mutually exclusive "
                             "(Megatron TP assumes a full local sequence)")
        if tp > 1:
            # Megatron block body: kernels arrive as local column/row slices
            # (see the placement specs in build_spmd_pipeline), two psums
            # over 'tp' per block — pp x dp x tp in ONE compiled program
            from .tensor import family_tp_plan
            _, tp_local = family_tp_plan(cfg)

            def block_apply(bp, x):
                return tp_local(bp, x, cfg, "tp")
        elif sp > 1:
            # sequence-parallel block body: activations stay sequence-
            # sharded [b, S/sp, D]; every sublayer is token-local except
            # the attention core, which runs as the exact sp core selected
            # by sp_kind (ring ppermute streaming or Ulysses all-to-all —
            # parallel/sequence.py::resolve_sp_core)
            from ..models.layers import self_attention
            from .sequence import resolve_sp_core
            core = partial(resolve_sp_core(self.sp_kind,
                                           cfg.num_attention_heads, sp),
                           axis_name="sp")

            def sp_attention(qkv, x, num_heads, causal=False):
                # reuse the family projection code; only the core changes
                # (ring/Ulysses cores handle causal masking themselves)
                c = partial(core, causal=True) if causal else core
                return self_attention(qkv, x, num_heads, core_fn=c)

            def block_apply(bp, x):
                for sub in range(4):
                    x = family.sublayer(bp, sub, x, cfg,
                                        attention_fn=sp_attention)
                return x
        else:
            def block_apply(bp, x):
                for sub in range(4):
                    x = family.sublayer(bp, sub, x, cfg)
                return x

        if self.remat:
            # rematerialize per BLOCK under jax.grad: the backward saves
            # only block-boundary activations and recomputes the sublayer
            # intermediates — without this, training ViT-L on one chip
            # needs ~40 GB of tick activations vs ~16 GB HBM (measured);
            # a no-op for inference (no grad, nothing to save)
            block_apply = jax.checkpoint(block_apply)

        def run_blocks(blocks, n_valid, x):
            # unrolled over per-block pytrees: a block reads its own arrays,
            # where a scan over the stack slices 25 MB out of it every
            # iteration (ViT-L; models/shard.py::shard_apply has the
            # measured pair). Slots every stage fills run unconditionally;
            # only some stage's padding needs the device-side test
            for j, bp in enumerate(blocks):
                if j < min_b:
                    x = block_apply(bp, x)
                else:
                    x = jax.lax.cond(j < n_valid, partial(block_apply, bp),
                                     lambda c: c, x)
            return x

        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        # -- edge codec: uniform bitwidth (direct) or mixed (lax.switch over
        #    a uniform padded uint32 wire buffer; SPMD shapes must match
        #    across devices, so the buffer is sized for the widest edge) ----
        edge_bits = tuple(self.stage_bits[i] for i in range(n_stages - 1))
        uniform = len(set(edge_bits)) <= 1
        if uniform:
            quant_bit = edge_bits[0] if edge_bits else 0

            def encode(h, stage):
                if quant_bit == 0:
                    return h
                # fused Pallas epilogue when enabled (ops/fused_quant.py):
                # the encode rides the stage's last matmul instead of a
                # separate XLA fusion — bit-identical either way
                return fused_quant.encode_outerdim(h, quant_bit)

            def decode(e, stage):
                if quant_bit == 0:
                    return e
                return fused_quant.decode_outerdim(e)

            def zero_carry(dt=None):
                return encode(jnp.zeros(hidden_local.shape,
                                        dt or hidden_local.dtype), 0)
        else:
            n_vals = int(np.prod(hidden_local.shape[1:]))
            itemsize = jnp.dtype(hidden_local.dtype).itemsize
            distinct = sorted(set(edge_bits))
            words_for = {
                wb: (quant_ops.packed_words(n_vals, wb) if wb > 0
                     else _raw_words(n_vals, itemsize)) for wb in distinct}
            max_words = max(words_for.values())

            def make_enc(wb):
                def enc(h):
                    if wb == 0:
                        data = _bitcast_to_words(h)
                        scale = jnp.ones((b_local,), jnp.float32)
                        shift = jnp.zeros((b_local,), jnp.float32)
                    else:
                        q = fused_quant.encode_outerdim(h, wb)
                        data, scale, shift = q.data, q.scale, q.shift
                    pad = max_words - data.shape[1]
                    if pad:
                        data = jnp.pad(data, ((0, 0), (0, pad)))
                    return data, scale, shift
                return enc

            def make_dec(wb):
                def dec(payload):
                    data, scale, shift = payload
                    if wb == 0:
                        return _bitcast_from_words(
                            data, hidden_local.shape, hidden_local.dtype)
                    q = quant_ops.QuantizedTensor(
                        data=data[:, :words_for[wb]], scale=scale, shift=shift,
                        shape=hidden_local.shape, bit=wb)
                    return fused_quant.decode_outerdim(q).astype(
                        hidden_local.dtype)
                return dec

            enc_branches = [make_enc(wb) for wb in distinct]
            dec_branches = [make_dec(wb) for wb in distinct]
            # stage i's OUT edge uses edge_bits[i]; its IN edge uses
            # edge_bits[i-1] (clamped: stage 0's in-edge / the last stage's
            # out-edge values are never consumed)
            out_branch = jnp.asarray(
                [distinct.index(edge_bits[min(i, n_stages - 2)])
                 for i in range(n_stages)], jnp.int32)
            in_branch = jnp.asarray(
                [distinct.index(edge_bits[max(i - 1, 0)])
                 for i in range(n_stages)], jnp.int32)

            def encode(h, stage):
                return jax.lax.switch(out_branch[stage], enc_branches, h)

            def decode(payload, stage):
                return jax.lax.switch(in_branch[stage], dec_branches, payload)

            def zero_carry(dt=None):
                del dt   # the mixed-bits wire buffer is dtype-fixed
                return (jnp.zeros((b_local, max_words), jnp.uint32),
                        jnp.zeros((b_local,), jnp.float32),
                        jnp.zeros((b_local,), jnp.float32))

        def permute_payload(payload):
            if n_stages == 1:
                return payload
            return jax.tree_util.tree_map(
                lambda t: jax.lax.ppermute(t, "stage", fwd_perm), payload)

        def spmd_body(params, stacked_inputs):
            # local views: blocks [1, max_b, ...] (stage-sharded), inputs
            # [M, B/dp, ...] (dp-sharded), embed/final replicated. The
            # stage's stack is taken apart HERE, once a call: outside the
            # tick scan the per-block arrays are its loop invariants. The
            # same static slice inside `tick` would be made every tick
            blocks = [jax.tree_util.tree_map(lambda x, j=j: x[0, j],
                                             params["blocks"])
                      for j in range(max_b)]
            n_valid = params["n_blocks"][0]
            stage = jax.lax.axis_index("stage")
            is_first = stage == 0
            is_last = stage == n_stages - 1

            # activation dtype follows THIS call's params/inputs, not the
            # build-time pipeline params: the training step's mixed-
            # precision mode runs this same program on a bfloat16 cast of
            # the float32 masters, so the zeros branches and the scan
            # carry must match the cast, not the masters
            act_dtype = jax.eval_shape(
                partial(family.embed, cfg=cfg), params["embed"],
                stacked_inputs[0]).dtype

            # Embeddings for all microbatches — computed only on the first
            # stage (runtime branch on the device-local stage index); other
            # stages carry zeros of the same shape.
            def do_embed(si):
                return jax.vmap(
                    lambda u: family.embed(params["embed"], u, cfg))(si)

            if sp > 1:
                # Long-context memory: pre-embedding all M microbatches at
                # FULL sequence would give stage 0 an [M, b, S, D] buffer —
                # exactly the scaling sp sheds. Instead embed one microbatch
                # per tick (inside `tick` below, stage 0 only) and keep the
                # local chunk. Trade: embed joins stage 0's tick latency
                # (small vs a stage of blocks); the full-seq [b, S, D]
                # intermediate is transient.
                sp_idx = jax.lax.axis_index("sp")

                def embed_chunk(si_u):
                    full = family.embed(params["embed"], si_u, cfg)
                    return jax.lax.dynamic_slice_in_dim(
                        full, sp_idx * s_local, s_local, axis=1)

                def embed_at(t):
                    return jax.lax.cond(
                        is_first,
                        lambda u: embed_chunk(u),
                        lambda u: jnp.zeros(hidden_local.shape,
                                            act_dtype),
                        stacked_inputs[t])
            else:
                embedded = jax.lax.cond(
                    is_first, do_embed,
                    lambda si: jnp.zeros(
                        (n_ubatch, b_local, seq_total)
                        + embed_shape.shape[2:],
                        act_dtype), stacked_inputs)

                def embed_at(t):
                    return embedded[t]

            outputs0 = jnp.zeros((n_ubatch,) + out_shape.shape, out_shape.dtype)

            def tick(carry, t):
                # `in_flight` is what this stage finished in the last tick.
                # With the lead, what it computes on is what `landed` in
                # the last tick, and nothing in this tick reads `arriving`:
                # the transfer has the stage's blocks to hide behind.
                # Without, `landed` is empty and the tick waits for it
                in_flight, landed, outputs = carry
                arriving = permute_payload(in_flight)
                recv = decode(landed[0] if lead else arriving, stage)
                in_idx = jnp.clip(t, 0, n_ubatch - 1)
                x = jnp.where(is_first, embed_at(in_idx), recv)
                # Every stage runs its blocks every tick, including fill
                # ticks (garbage in-flight) and drain ticks (stage 0 on a
                # clamped stale input). This is deliberate: ticks are
                # lockstep across the stage axis and some stage does valid
                # work in every tick, so gating invalid stages (lax.cond)
                # cannot shorten any tick — it would only spend the saved
                # FLOPs on idle waiting at the same wall-clock.
                h = run_blocks(blocks, n_valid, x)
                out_idx = t - (1 + lead) * (n_stages - 1)

                def fin(hh):
                    if sp > 1:
                        # pooler/classifier reads the full sequence (CLS at
                        # position 0 lives on sp rank 0): gather the chunks
                        # — quantized over ICI when --tp-quant-bits is set
                        # (ops/qcollectives.py), exact otherwise
                        if collective_bits:
                            from ..ops import qcollectives
                            hh = qcollectives.qall_gather(
                                hh, "sp", collective_bits, axis=1, tiled=True)
                        else:
                            hh = jax.lax.all_gather(hh, "sp", axis=1,
                                                    tiled=True)
                    return family.finalize(params["final"], hh, cfg).astype(
                        out_shape.dtype)

                # classifier head/pooler only on the last stage — for
                # ViT-Huge's 21843-way head that is a real matmul per tick
                logits = jax.lax.cond(
                    is_last, fin,
                    lambda hh: jnp.zeros(out_shape.shape, out_shape.dtype), h)
                updated = jax.lax.dynamic_update_slice(
                    outputs, logits[None].astype(outputs.dtype),
                    (jnp.clip(out_idx, 0, n_ubatch - 1),)
                    + (0,) * len(out_shape.shape))
                valid = jnp.logical_and(out_idx >= 0, is_last)
                outputs = jnp.where(valid, updated, outputs)
                return (encode(h, stage), (arriving,) * lead, outputs), None

            (_, _, outputs), _ = jax.lax.scan(
                tick, (zero_carry(act_dtype),
                       tuple(zero_carry(act_dtype) for _ in range(lead)),
                       outputs0), jnp.arange(n_ticks))
            # only the last stage wrote real outputs; fan them back out
            return jax.lax.psum(outputs, "stage")

        dp_spec = "dp" if dp > 1 else None
        in_specs = (
            {
                "embed": P(),
                "final": P(),
                "blocks": _stacked_block_specs(cfg, self.params["blocks"],
                                               tp),
                "n_blocks": P("stage"),
            },
            P(None, dp_spec),
        )
        out_spec = P(None, dp_spec)
        fn = jax.jit(jax_compat.shard_map(spmd_body, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_spec))
        return fn


def build_spmd_pipeline(family: FamilySpec, cfg: TransformerConfig,
                        partition: Sequence[Tuple[int, int]],
                        stage_params: Sequence[Dict], mesh: Mesh,
                        quant_bit=0, sp_kind: str = "ring",
                        remat: bool = False) -> SpmdPipeline:
    """Assemble an `SpmdPipeline` from per-stage shard parameter pytrees.

    `stage_params[i]` is the pytree built by a family loader for stage i's
    `ShardConfig` (block-aligned). Stage 0 must carry 'embeddings', the last
    stage 'final'; per-stage 'blocks' stacks are zero-padded to the deepest
    stage, and the slots past the shallowest stage's count are masked at run
    time (`min_blocks`; an even partition has none).

    `quant_bit`: an int applied to every inter-stage edge, or a per-stage
    sequence where entry i quantizes the edge leaving stage i (reference
    `-q` list semantics, runtime.py:652-656; the final entry is the result
    edge and is forced to 0).
    """
    prom.count_jax_compiles()
    n_stages = len(partition)
    if isinstance(quant_bit, (list, tuple)):
        if len(quant_bit) != n_stages:
            raise ValueError(f"quant_bit list length {len(quant_bit)} != "
                             f"{n_stages} stages")
        stage_bits = tuple(int(b) for b in quant_bit[:-1]) + (0,)
    else:
        stage_bits = (int(quant_bit),) * max(n_stages - 1, 0) + (0,)
    if mesh.shape["stage"] != n_stages:
        raise ValueError(f"mesh 'stage' axis {mesh.shape['stage']} != "
                         f"{n_stages} pipeline stages")
    partition_to_blocks(partition)  # validates block alignment

    blocks_list = []
    n_blocks = []
    for i, p in enumerate(stage_params):
        if "blocks" not in p:
            raise ValueError(f"stage {i} has no full blocks; SPMD pipeline "
                             f"requires block-aligned partitions")
        if isinstance(p["blocks"], (tuple, list)):
            raise ValueError(
                f"stage {i} params use the unrolled (tuple) block layout; "
                "the SPMD pipeline stacks blocks across the stage axis — "
                "build stage params with module_shard_factory(..., "
                "unroll=False) or family loaders directly")
        blocks_list.append(p["blocks"])
        n_blocks.append(jax.tree_util.tree_leaves(p["blocks"])[0].shape[0])
    max_b, min_b = max(n_blocks), min(n_blocks)
    nonzero = [b for b in stage_bits[:-1] if b > 0]
    if nonzero and any(b == 0 for b in stage_bits[:-1]):
        logger.warning(
            "SPMD per-stage quant bits %s mix raw (0) and quantized edges: "
            "the uniform SPMD wire buffer is padded to the raw edge's size, "
            "so quantized edges save no interconnect bandwidth in this "
            "configuration (quantization error still applies)", stage_bits)

    tp = mesh.shape.get("tp", 1)
    if tp > 1:
        if cfg.num_attention_heads % tp or cfg.intermediate_size % tp \
                or cfg.kv_heads % tp:
            raise ValueError(
                f"mesh tp={tp} must divide attention heads "
                f"({cfg.num_attention_heads}), kv heads ({cfg.kv_heads}), "
                f"and intermediate size ({cfg.intermediate_size})")
    if cfg.n_experts and (tp > 1 or mesh.shape.get("sp", 1) > 1):
        # tp: expert kernels shard over 'ep', not the Megatron table;
        # sp: routing over a local sequence chunk changes the capacity
        # semantics (per-chunk instead of global top-C) — refuse rather
        # than silently compute something different from the oracle
        raise NotImplementedError(
            "MoE blocks do not compose with the 'tp'/'sp' mesh axes")
    # place parameters: blocks stacked over the stage axis and stage-sharded
    # (and Megatron tp-sharded when the mesh has a tp axis), embed/final
    # replicated
    with telemetry.startup("weights_place"):
        blocks = _pad_stack(blocks_list, max_b)
        block_specs = _stacked_block_specs(cfg, blocks, tp)
        params = {
            "embed": jax.device_put(stage_params[0]["embeddings"],
                                    NamedSharding(mesh, P())),
            "final": jax.device_put(stage_params[-1]["final"],
                                    NamedSharding(mesh, P())),
            "blocks": jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                blocks, block_specs),
            "n_blocks": jax.device_put(jnp.asarray(n_blocks, jnp.int32),
                                       NamedSharding(mesh, P("stage"))),
        }
    _M_STAGE_BLOCKS.set(min_b, kind="unconditional")
    _M_STAGE_BLOCKS.set(max_b - min_b, kind="masked")
    return SpmdPipeline(family=family, cfg=cfg, mesh=mesh, n_stages=n_stages,
                        max_blocks=max_b, min_blocks=min_b, params=params,
                        stage_bits=stage_bits, sp_kind=sp_kind,
                        remat=remat)


def make_pipeline_mesh(n_stages: int, dp: int = 1, tp: int = 1, sp: int = 1,
                       devices: Optional[Sequence[jax.Device]] = None,
                       stage_ranks: Optional[Sequence[int]] = None) -> Mesh:
    """Build a ('dp', 'stage'[, 'tp'|'sp']) mesh: the within-stage axis (tp
    Megatron sharding or sp ring attention) innermost — its per-block
    collectives ride adjacent ICI links — stage next (ppermute edges ride
    neighboring links). tp and sp are mutually exclusive.

    `stage_ranks[i]` places stage i on `devices[stage_ranks[i]]` (reference
    `-r` rank-order semantics, runtime.py:657-687); requires dp=tp=sp=1 and
    distinct ranks.
    """
    if tp > 1 and sp > 1:
        raise ValueError("tp and sp mesh axes are mutually exclusive")
    if devices is None:
        devices = jax.devices()
    if stage_ranks is not None:
        if dp != 1 or tp != 1 or sp != 1:
            raise ValueError("stage_ranks requires dp=1, tp=1 and sp=1")
        if len(stage_ranks) != n_stages:
            raise ValueError(f"stage_ranks length {len(stage_ranks)} != "
                             f"{n_stages} stages")
        if len(set(stage_ranks)) != n_stages:
            raise ValueError(f"stage_ranks must be distinct: {stage_ranks}")
        if max(stage_ranks) >= len(devices):
            raise ValueError(f"stage rank {max(stage_ranks)} out of range "
                             f"({len(devices)} devices)")
        arr = np.asarray([devices[r] for r in stage_ranks]).reshape(1, n_stages)
        return Mesh(arr, ("dp", "stage"))
    inner, inner_name = (tp, "tp") if tp > 1 else (sp, "sp")
    need = n_stages * dp * inner
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    if inner > 1:
        arr = np.asarray(devices[:need]).reshape(dp, n_stages, inner)
        return Mesh(arr, ("dp", "stage", inner_name))
    arr = np.asarray(devices[:need]).reshape(dp, n_stages)
    return Mesh(arr, ("dp", "stage"))
