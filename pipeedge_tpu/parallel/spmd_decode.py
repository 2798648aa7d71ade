"""SPMD wave decoding: continuous batching as ONE XLA program per phase.

The host-driven `ContinuousBatcher` (parallel/batcher.py) dispatches one
stage program per (stage, tick) — n_stages dispatches per tick, with the
host in the loop. Each dispatch costs fixed host overhead, which can dwarf
a decode step's compute. This module compiles the ENTIRE wave schedule into
two `shard_map` programs over a ('stage',) mesh:

- **prefill program**: R = n_stages requests enter stage 0 on successive
  ticks; each stage prefills a different request per tick (full-prompt
  pass), hidden states hop stage-to-stage via `lax.ppermute` over ICI,
  and the last stage emits each request's first greedy token. 2K-1 ticks.
- **decode program**: the steady-state wave — per tick, stage i decodes
  the request whose wave is at stage i (`req = (t - i) mod K`), so every
  stage works every tick and the fleet emits ~one token per tick
  (min(S, K)x a solo stream, with ZERO host round-trips inside the
  generation: one `lax.scan` over all (N-1)*K + K-1 ticks).

Design notes (mirrors parallel/spmd.py's forward pipeline):
- Stage-stacked zero-padded blocks with an `n_blocks` validity count;
  embeddings/finalize run under `lax.cond` on the device-local stage
  index, so only stage 0 pays the embed and only the last stage pays the
  LM-head matmul per tick.
- Per-stage KV caches hold every request's rows for that stage's blocks:
  leaf [stage, max_b, R, B, T, H*Dh], sharded over 'stage'. A tick
  dynamic-slices its request's cache, runs the shared cached block step
  (parallel/decode.py `_block_step` — one attention/cache semantics for
  host and SPMD decode), and writes back gated on tick validity so
  fill/drain garbage never corrupts a cache.
- Wave bookkeeping is arithmetic, not state: request r's decode wave m
  runs pos = S_p + m - 1, and stage i at tick t serves req (t-i) mod K at
  wave floor((t-i)/K)+1 — every device derives it from t, keeping all
  replicated state in lockstep. New tokens broadcast last-stage -> all
  via one psum (the only collective besides the edge ppermute).

Scope: greedy or temperature/top-k sampled decoding (per-slot rng chains
split once per picked token, in lockstep on every device — the host
generate() discipline), R == n_stages request slots, equal prompt
lengths/budgets per slot (the static-shape steady state; the host-driven
batcher handles ragged arrivals). Token-identical to per-request
`DecodePipeline.generate` (tests/test_spmd_decode.py).

Not a serving executor: tools/serve.py runs `ContinuousBatcher` and
nothing else; this engine is reached from tools/generate.py `--spmd-wave`
and `__graft_entry__.py` only, and stays or goes with the four-chip
decoder cell (ROADMAP R1, D2).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..utils import jax_compat
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..models import ShardConfig, stage_cache
from ..models.layers import TransformerConfig
from ..models.shard import FamilySpec
from . import decode as dec
from .spmd import _pad_stack, partition_to_blocks


class SpmdDecodePipeline:
    """Wave-scheduled decoding compiled over a ('stage',) mesh.

    `generate(ids, new_tokens)` takes ids [R, B, S_p] — R = n_stages
    request slots decoded concurrently — and returns [R, B, S_p + N].
    """

    def __init__(self, family: FamilySpec, cfg: TransformerConfig,
                 partition: Sequence[Tuple[int, int]],
                 stage_params: Sequence[Dict], mesh: Mesh, max_len: int,
                 dtype=jnp.float32, edge_bits: int = 0):
        total = 4 * cfg.num_hidden_layers
        dec.validate_partition(partition, total)
        dec.validate_capacity(cfg, max_len)
        block_ranges = partition_to_blocks(partition)
        n_stages = len(partition)
        if mesh.shape["stage"] != n_stages:
            raise ValueError(f"mesh 'stage' axis {mesh.shape['stage']} != "
                             f"{n_stages} pipeline stages")
        if getattr(family, "cache_leaves", None) is not None:
            raise NotImplementedError(
                f"the {family.name} family names its own cache leaves (a "
                "state a request, rows every few positions): the SPMD wave "
                "decoder stacks the plain k, v pair over its stages")
        if cfg.n_experts:
            raise NotImplementedError(
                "SPMD wave decode covers dense families; MoE decodes via "
                "DecodePipeline(ep_mesh/tp_ep_mesh)")
        if edge_bits not in (0, 2, 4, 6, 8, 16):
            raise ValueError(f"edge_bits must be one of 0/2/4/6/8/16, got "
                             f"{edge_bits}")
        self.family, self.cfg, self.mesh = family, cfg, mesh
        self.n_stages, self.max_len, self.dtype = n_stages, max_len, dtype
        self.edge_bits = edge_bits

        stage_blocks, n_blocks = [], []
        embed = final = None
        for i, p in enumerate(stage_params):
            p = dict(p)
            p["blocks"] = dec.stage_blocks(p)
            stage_blocks.append(p["blocks"])
            n_blocks.append(block_ranges[i][1] - block_ranges[i][0] + 1)
            if i == 0:
                embed = p["embeddings"]
            if i == n_stages - 1:
                final = p["final"]
        if embed is None or final is None:
            raise ValueError("stage 0 must carry 'embeddings' and the last "
                             "stage 'final'")
        self.max_b = max(n_blocks)
        self._n_blocks = tuple(n_blocks)   # per-stage, for prefix sigs
        # place params ONCE with the same shardings the programs compile
        # against (spmd.py's placement discipline): blocks/n_blocks
        # stage-sharded, embed/final replicated. Without this the padded
        # stack would materialize on one device and reshard every call.
        from jax.sharding import NamedSharding
        params = {
            "embed": embed, "final": final,
            "blocks": _pad_stack(stage_blocks, self.max_b),
            "n_blocks": jnp.asarray(n_blocks, jnp.int32),
        }
        shard = NamedSharding(mesh, P("stage"))
        repl = NamedSharding(mesh, P())
        self.params = {
            "embed": jax.device_put(params["embed"], repl),
            "final": jax.device_put(params["final"], repl),
            "blocks": jax.tree_util.tree_map(
                lambda x: jax.device_put(x, shard), params["blocks"]),
            "n_blocks": jax.device_put(params["n_blocks"], shard),
        }
        self._programs: Dict = {}
        self._cache_init: Dict = {}

    # -- shared per-tick pieces -------------------------------------------

    def _run_blocks(self, blocks, n_valid, x, bcache, pos, prefill):
        """Scan this stage's (padded) blocks over x with cache read/update;
        padded slots pass through unchanged. The block body is the
        family's cached step when it provides one (llama RoPE/GQA/SwiGLU),
        else the default GPT-2-shaped step — same dispatch as the host
        decode pipeline."""
        cfg = self.cfg
        block_fn = getattr(self.family, "cached_block_step", None) \
            or dec._block_step

        def step(carry, xs):
            j, bp, bc = xs

            def live(args):
                # the block steps read a stacked cache by layer index and
                # hand back the rows to write (stage_cache.LayerCache): give
                # them this layer as a stack of one
                c, cache_j = args
                one = jax.tree_util.tree_map(lambda a: a[None], cache_j)
                y, bc = block_fn(bp, c, stage_cache.LayerCache(one, 0), pos,
                                 cfg, prefill)
                rows = jax.tree_util.tree_map(lambda a: a[None], bc.rows)
                one = stage_cache.write_rows(one, rows, 0 if prefill else pos)
                return y, jax.tree_util.tree_map(lambda a: a[0], one)

            out, bc_new = jax.lax.cond(
                j < n_valid, live, lambda args: args, (carry, bc))
            return out, bc_new

        idx = jnp.arange(self.max_b)
        return jax.lax.scan(step, x, (idx, blocks, bcache))

    def _cache_slice(self, caches, req):
        """caches leaf [max_b, R, B, T, H*Dh] -> request slice [max_b, B,..]."""
        return jax.tree_util.tree_map(
            lambda c: jax.lax.dynamic_index_in_dim(c, req, axis=1,
                                                   keepdims=False), caches)

    def _cache_write(self, caches, bcache, req, valid):
        def wr(c, new):
            new = jnp.where(valid, new, jax.lax.dynamic_index_in_dim(
                c, req, axis=1, keepdims=False))
            return jax.lax.dynamic_update_index_in_dim(
                c, new.astype(c.dtype), req, axis=1)

        return jax.tree_util.tree_map(wr, caches, bcache)

    def _zero_caches(self, r_slots, batch):
        """Stage-sharded zero caches, allocated ALREADY sharded: a plain
        jnp.zeros would materialize every stage's cache on one device (an
        HBM spike ~n_stages x the per-device share) before resharding.
        The jitted init is cached per shape so repeated generate() calls
        hit the jit cache instead of recompiling."""
        if (r_slots, batch) not in self._cache_init:
            from jax.sharding import NamedSharding
            shape = (self.n_stages, self.max_b, r_slots, batch,
                     self.max_len, self.cfg.kv_heads * self.cfg.head_dim)
            self._cache_init[(r_slots, batch)] = jax.jit(
                partial(jnp.zeros, shape, self.dtype),
                out_shardings=NamedSharding(self.mesh, P("stage")))
        zeros = self._cache_init[(r_slots, batch)]
        return {"k": zeros(), "v": zeros()}

    def _broadcast_prefix_caches(self, handle, r_slots, batch):
        """Tile a `precompute_prefix` handle's [stage, max_b, 1, 1, T, ..]
        cache to every (slot, batch row) — sharded on allocation, like
        `_zero_caches` (prompt caching's batch-tiling rule)."""
        from jax.sharding import NamedSharding
        key = ("pfx-tile", r_slots, batch)
        if key not in self._cache_init:
            shape = (self.n_stages, self.max_b, r_slots, batch,
                     self.max_len, self.cfg.kv_heads * self.cfg.head_dim)
            self._cache_init[key] = jax.jit(
                partial(jnp.broadcast_to, shape=shape),
                out_shardings=NamedSharding(self.mesh, P("stage")))
        tile = self._cache_init[key]
        return {k: tile(v) for k, v in handle["caches"].items()}

    # -- compiled phases ---------------------------------------------------

    @staticmethod
    def _local(params, caches):
        blocks = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
        caches = {k: v[0] for k, v in caches.items()}
        n_valid = params["n_blocks"][0]
        stage = jax.lax.axis_index("stage")
        return blocks, caches, n_valid, stage

    def _make_split_for(self, r_slots):
        """Split the key of the request at the LAST stage this tick —
        computed identically on every device (replicated rngs, tick
        arithmetic), so the fleet's rng state stays in lockstep. One
        split per picked token, the host generate() discipline. ONE
        definition for all three wave programs (prefill/decode/span)."""
        k_stages = self.n_stages

        def split_for(rngs, t):
            req_last = jnp.mod(t - (k_stages - 1), r_slots)
            key, sub = jax.random.split(rngs[req_last])
            return req_last, jax.lax.dynamic_update_index_in_dim(
                rngs, key, req_last, axis=0), sub

        return split_for

    def _edge_codec(self):
        """Stage-edge payload codec: QuantPipe activation compression on
        the big ([B, S, D]-sized) ppermute hops when `edge_bits` is set —
        shared by the prefill wave AND the span wave so prefix-seeded
        suffix passes stay numerically identical to monolithic runs."""
        from ..ops import quant as quant_ops
        bit = self.edge_bits

        def enc(h):
            return h if bit == 0 else \
                quant_ops.tensor_encode_outerdim(h, bit)

        def decode_payload(payload):
            return payload if bit == 0 else \
                quant_ops.tensor_decode_outerdim(payload).astype(self.dtype)

        return enc, decode_payload

    def _specs(self):
        blocks_spec = jax.tree_util.tree_map(
            lambda _: P("stage"), self.params["blocks"])
        p_spec = {"embed": P(), "final": P(), "blocks": blocks_spec,
                  "n_blocks": P("stage")}
        return p_spec, {"k": P("stage"), "v": P("stage")}

    def _prefill_prog(self, r_slots, batch, prompt_len, temperature=0.0,
                      top_k=0):
        """Cached compiled prefill wave — keyed WITHOUT new_tokens (the
        prefill program doesn't depend on it), so every generation
        length and the speculative driver share one compile."""
        key = ("prefill", r_slots, batch, prompt_len, float(temperature),
               int(top_k))
        if key not in self._programs:
            self._programs[key] = self._build_prefill(
                r_slots, batch, prompt_len, float(temperature),
                int(top_k))
        return self._programs[key]

    def _decode_prog(self, r_slots, batch, prompt_len, new_tokens,
                     temperature=0.0, top_k=0):
        key = ("decode", r_slots, batch, prompt_len, new_tokens,
               float(temperature), int(top_k))
        if key not in self._programs:
            self._programs[key] = self._build_decode(
                r_slots, batch, prompt_len, new_tokens,
                float(temperature), int(top_k))
        return self._programs[key]

    def _build_prefill(self, r_slots: int, batch: int, prompt_len: int,
                       temperature: float, top_k: int):
        family, cfg, k_stages = self.family, self.cfg, self.n_stages
        d = cfg.hidden_size
        pick = dec.make_token_picker(temperature, top_k)
        local = self._local
        split_for = self._make_split_for(r_slots)

        def prefill_body(params, ids, caches, rngs):
            """Wave-prefill all R requests; returns (caches, token1 [R, B],
            advanced rng keys). With `edge_bits`, the [B, S_p, D] prompt
            hops — the wave decoder's big payloads — cross the stage edge
            as packed uint32 (QuantPipe activation compression riding the
            ppermute, like the forward SPMD pipeline's quantized edges);
            the [B, 1, D] decode-step hops stay raw (metadata-sized)."""
            blocks, caches, n_valid, stage = local(params, caches)
            is_first = stage == 0
            is_last = stage == k_stages - 1
            # QuantizedTensor is a registered pytree (static shape/bit aux),
            # so the encoded payload rides the tree_map'd ppermute directly
            # — the same discipline as spmd.py's uniform quantized edges
            edge_enc, edge_dec = self._edge_codec()
            tokens0 = jnp.zeros((r_slots, batch), jnp.int32)

            def tick(carry, t):
                hidden, caches, tokens, rngs = carry
                recv = jax.tree_util.tree_map(
                    lambda leaf: jax.lax.ppermute(
                        leaf, "stage",
                        [(i, (i + 1) % k_stages) for i in range(k_stages)]),
                    hidden)
                req = jnp.mod(t - stage, r_slots)
                valid = jnp.logical_and(t - stage >= 0,
                                        t - stage < r_slots)
                # stage 0 embeds its request's prompt; every other stage
                # consumes the ppermuted (possibly packed) hop
                x = jax.lax.cond(
                    is_first,
                    lambda r: family.embed(
                        params["embed"],
                        jax.lax.dynamic_index_in_dim(ids, r, 0, False),
                        cfg).astype(self.dtype),
                    lambda r: edge_dec(recv), req)
                bcache = self._cache_slice(caches, req)
                h, bcache = self._run_blocks(blocks, n_valid, x, bcache,
                                             0, prefill=True)
                caches = self._cache_write(caches, bcache, req, valid)
                req_last, rngs_new, sub = split_for(rngs, t)
                valid_last = jnp.logical_and(t >= k_stages - 1,
                                             t - (k_stages - 1) < r_slots)
                rngs = jnp.where(valid_last, rngs_new, rngs)

                def fin(hh):
                    logits = family.finalize(params["final"], hh, cfg)
                    return pick(logits[:, prompt_len - 1].astype(
                        jnp.float32), sub).astype(jnp.int32)

                tok = jax.lax.cond(
                    is_last, fin,
                    lambda hh: jnp.zeros((batch,), jnp.int32), h)
                write = jnp.logical_and(valid, is_last)
                upd = jax.lax.dynamic_update_index_in_dim(
                    tokens, tok, req, axis=0)
                tokens = jnp.where(write, upd, tokens)
                return (edge_enc(h), caches, tokens, rngs), None

            hidden0 = edge_enc(jnp.zeros((batch, prompt_len, d),
                                         self.dtype))
            (_, caches, tokens, rngs), _ = jax.lax.scan(
                tick, (hidden0, caches, tokens0, rngs),
                jnp.arange(r_slots + k_stages - 1))
            # only the last stage wrote tokens; fan out to every device
            return ({k: v[None] for k, v in caches.items()},
                    jax.lax.psum(tokens, "stage"), rngs)

        p_spec, c_spec = self._specs()
        return jax.jit(jax_compat.shard_map(
            prefill_body, mesh=self.mesh,
            in_specs=(p_spec, P(), c_spec, P()),
            out_specs=(c_spec, P(), P())))

    def _build_decode(self, r_slots: int, batch: int, prompt_len: int,
                      new_tokens: int, temperature: float, top_k: int):
        family, cfg, k_stages = self.family, self.cfg, self.n_stages
        d = cfg.hidden_size
        pick = dec.make_token_picker(temperature, top_k)
        local = self._local
        split_for = self._make_split_for(r_slots)

        def decode_body(params, token1, caches, rngs):
            """All remaining waves: returns tokens [R, new_tokens, B]."""
            blocks, caches, n_valid, stage = local(params, caches)
            is_first = stage == 0
            is_last = stage == k_stages - 1
            n_waves = new_tokens - 1     # wave m in [1, n_waves] -> token m+1

            def embed_tok(tok, pos):
                # the family's single-token embedding rule, shared with
                # the host stage runner (llama: wte only; default wte+wpe)
                tok_embed = getattr(family, "decode_embed", None) \
                    or dec.single_token_embed
                return tok_embed(params["embed"], tok, pos).astype(
                    self.dtype)

            outputs0 = jnp.zeros((r_slots, new_tokens, batch), jnp.int32)
            outputs0 = outputs0.at[:, 0].set(token1)

            def tick(carry, t):
                hidden, caches, cur_tok, outputs, rngs = carry
                recv = jax.lax.ppermute(
                    hidden, "stage",
                    [(i, (i + 1) % k_stages) for i in range(k_stages)])
                req = jnp.mod(t - stage, r_slots)
                wave = jnp.floor_divide(t - stage, r_slots) + 1
                valid = jnp.logical_and(t - stage >= 0, wave <= n_waves)
                pos = prompt_len + wave - 1

                x = jax.lax.cond(
                    is_first,
                    lambda a: embed_tok(*a),
                    lambda a: recv,
                    (cur_tok[req], pos))
                bcache = self._cache_slice(caches, req)
                h, bcache = self._run_blocks(blocks, n_valid, x, bcache,
                                             pos, prefill=False)
                caches = self._cache_write(caches, bcache, req, valid)
                # the request at the LAST stage this tick (device-uniform)
                req_last, rngs_new, sub = split_for(rngs, t)
                wave_last = jnp.floor_divide(t - (k_stages - 1), r_slots) + 1
                valid_last = jnp.logical_and(t >= k_stages - 1,
                                             wave_last <= n_waves)
                rngs = jnp.where(valid_last, rngs_new, rngs)

                def fin(hh):
                    logits = family.finalize(params["final"], hh, cfg)
                    return pick(logits[:, 0].astype(jnp.float32),
                                sub).astype(jnp.int32)

                tok = jax.lax.cond(
                    is_last, fin,
                    lambda hh: jnp.zeros((batch,), jnp.int32), h)
                # broadcast the new token to every stage (one psum)
                tok_all = jax.lax.psum(tok, "stage")
                upd = jax.lax.dynamic_update_index_in_dim(
                    cur_tok, tok_all, req_last, axis=0)
                cur_tok = jnp.where(valid_last, upd, cur_tok)
                out_upd = jax.lax.dynamic_update_slice(
                    outputs, tok_all[None, None],
                    (req_last, jnp.clip(wave_last, 0, new_tokens - 1), 0))
                outputs = jnp.where(valid_last, out_upd, outputs)
                return (h, caches, cur_tok, outputs, rngs), None

            hidden0 = jnp.zeros((batch, 1, d), self.dtype)
            n_ticks = n_waves * r_slots + k_stages - 1
            (_, _, _, outputs, _), _ = jax.lax.scan(
                tick, (hidden0, caches, token1, outputs0, rngs),
                jnp.arange(n_ticks))
            return outputs

        p_spec, c_spec = self._specs()
        return jax.jit(jax_compat.shard_map(
            decode_body, mesh=self.mesh,
            in_specs=(p_spec, P(), c_spec, P()),
            out_specs=P()))

    def _build_span(self, r_slots: int, batch: int, span_k: int,
                    emit: str, temperature: float = 0.0, top_k: int = 0):
        """ONE wave over K-token spans: tick t, stage i runs slot
        (t-i) mod R's [B, K] span at cache offset `pos` (a traced scalar
        — one compiled program serves every round/offset). The span
        semantics are the host pipeline's `extend` (K/V written at
        [pos, pos+K), causal within the span, full history before it) —
        the same `_block_step` body, so wave spans and host spans can
        never diverge.

        `emit='pick_last'` returns (caches, picked last-row token [R, B],
        advanced rngs) — the prefix-seeded SUFFIX prompt pass.
        `emit='argmax_all'` returns (caches, greedy argmax of every span
        row [R, K, B]) — the speculative VERIFY primitive."""
        family, cfg, k_stages = self.family, self.cfg, self.n_stages
        d = cfg.hidden_size
        pick = dec.make_token_picker(temperature, top_k)
        local = self._local
        split_for = self._make_split_for(r_slots)

        def span_embed_slot(params, tok, pos):
            tok_embed = getattr(family, "span_embed", None) \
                or dec.span_embed
            return tok_embed(params["embed"], tok, pos).astype(self.dtype)

        def span_body(params, spans, caches, pos, rngs):
            blocks, caches, n_valid, stage = local(params, caches)
            is_first = stage == 0
            is_last = stage == k_stages - 1
            # span hops are prompt-sized [B, K, D]: the edge codec rides
            # them exactly like the prefill wave's, so prefix-seeded
            # suffix passes match monolithic runs on quantized-edge
            # pipelines too
            edge_enc, edge_dec = self._edge_codec()
            if emit == "pick_last":
                outputs0 = jnp.zeros((r_slots, batch), jnp.int32)
            else:
                outputs0 = jnp.zeros((r_slots, span_k, batch), jnp.int32)

            def tick(carry, t):
                hidden, caches, outputs, rngs_ = carry
                recv = jax.tree_util.tree_map(
                    lambda leaf: jax.lax.ppermute(
                        leaf, "stage",
                        [(i, (i + 1) % k_stages) for i in range(k_stages)]),
                    hidden)
                req = jnp.mod(t - stage, r_slots)
                valid = jnp.logical_and(t - stage >= 0,
                                        t - stage < r_slots)
                x = jax.lax.cond(
                    is_first,
                    lambda r: span_embed_slot(
                        params,
                        jax.lax.dynamic_index_in_dim(spans, r, 0, False),
                        pos),
                    lambda r: edge_dec(recv), req)
                bcache = self._cache_slice(caches, req)
                h, bcache = self._run_blocks(blocks, n_valid, x, bcache,
                                             pos, prefill=False)
                caches = self._cache_write(caches, bcache, req, valid)
                req_last, rngs_new, sub = split_for(rngs_, t)
                valid_last = jnp.logical_and(t >= k_stages - 1,
                                             t - (k_stages - 1) < r_slots)
                rngs_ = jnp.where(valid_last, rngs_new, rngs_)

                if emit == "pick_last":
                    def fin(hh):
                        logits = family.finalize(params["final"], hh, cfg)
                        return pick(logits[:, span_k - 1].astype(
                            jnp.float32), sub).astype(jnp.int32)

                    zero = jnp.zeros((batch,), jnp.int32)
                else:
                    def fin(hh):
                        logits = family.finalize(params["final"], hh, cfg)
                        return jnp.argmax(
                            logits.astype(jnp.float32),
                            -1).astype(jnp.int32).T        # [K, B]

                    zero = jnp.zeros((span_k, batch), jnp.int32)
                tok = jax.lax.cond(is_last, fin, lambda hh: zero, h)
                upd = jax.lax.dynamic_update_index_in_dim(
                    outputs, tok, req_last, axis=0)
                outputs = jnp.where(valid_last, upd, outputs)
                return (edge_enc(h), caches, outputs, rngs_), None

            hidden0 = edge_enc(jnp.zeros((batch, span_k, d), self.dtype))
            (_, caches, outputs, rngs), _ = jax.lax.scan(
                tick, (hidden0, caches, outputs0, rngs),
                jnp.arange(r_slots + k_stages - 1))
            return ({k: v[None] for k, v in caches.items()},
                    jax.lax.psum(outputs, "stage"), rngs)

        p_spec, c_spec = self._specs()
        return jax.jit(jax_compat.shard_map(
            span_body, mesh=self.mesh,
            in_specs=(p_spec, P(), c_spec, P(), P()),
            out_specs=(c_spec, P(), P())))

    def _prefix_sig(self) -> Tuple:
        """Cache-compatibility signature for wave prefix handles (the
        host pipeline's `_prefix_sig` discipline: a handle is only valid
        on a pipeline whose cache layout AND numerics match — per-stage
        block counts catch same-shape different-partition pipelines,
        edge_bits catches quantized-edge numerics)."""
        return ("spmd-prefix-v1", self._n_blocks, self.max_len,
                jax.dtypes.canonicalize_dtype(self.dtype).name,
                self.cfg.kv_heads, self.cfg.head_dim, self.edge_bits)

    def check_prefix(self, prefix) -> None:
        sig = prefix.get("sig") if isinstance(prefix, dict) else None
        if sig is None:
            raise ValueError(
                "prefix is not a precompute_prefix handle (no 'sig' "
                "stamp); build it with this pipeline's precompute_prefix")
        if sig != self._prefix_sig():
            raise ValueError(
                "prefix handle was built by an incompatible wave "
                f"pipeline: handle sig {sig} vs {self._prefix_sig()}")

    def precompute_prefix(self, prefix_ids) -> Dict:
        """Prefill a shared prompt PREFIX once through the wave pipeline
        (a one-slot, batch-1 wave); the handle's [stage, max_b, 1, 1, T,
        ..] cache rows tile to every (slot, row) at `generate(prefix=)`.
        Exactness matches the host pipeline's prefix contract (fp
        caches; suffix spans attend prefix K/V exactly as a monolithic
        prefill would)."""
        ids = jnp.asarray(prefix_ids, jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        if ids.ndim != 2 or ids.shape[0] != 1:
            raise ValueError("a shared prefix is one sequence [P] or "
                             f"[1, P]; got shape {ids.shape}")
        p_len = ids.shape[1]
        dec.validate_capacity(self.cfg, self.max_len, p_len, 1)
        prefill = self._prefill_prog(1, 1, p_len)
        caches = self._zero_caches(1, 1)
        rngs = jnp.stack([jax.random.PRNGKey(0)])
        caches, _token1, _ = prefill(self.params, ids[None], caches, rngs)
        return {"caches": caches, "len": p_len, "sig": self._prefix_sig()}

    def _span_fn(self, r_slots, batch, span_k, emit, temperature=0.0,
                 top_k=0):
        key = ("span", emit, r_slots, batch, span_k, float(temperature),
               int(top_k))
        if key not in self._programs:
            self._programs[key] = self._build_span(
                r_slots, batch, span_k, emit, float(temperature),
                int(top_k))
        return self._programs[key]

    def generate(self, ids, new_tokens: int, temperature: float = 0.0,
                 top_k: int = 0, seeds=None, prefix: Optional[Dict] = None):
        """Decode R = n_stages concurrent prompts [R, B, S_p] ->
        [R, B, S_p + new_tokens].

        `temperature=0` is greedy; otherwise each slot samples with its
        own rng chain seeded from `seeds[r]` (default: slot index), split
        once per picked token — request r's token stream is identical to
        `DecodePipeline.generate(ids[r], ..., seed=seeds[r])`.

        `prefix` (from `precompute_prefix`) seeds every slot's cache
        with a shared prompt prefix; `ids` is then each slot's SUFFIX
        [R, B, S_s], its prompt pass runs as ONE span wave at the prefix
        offset, and the returned array omits the prefix — the host
        pipeline's prefix contract, through the wave programs."""
        ids = jnp.asarray(ids, jnp.int32)
        if ids.ndim != 3 or ids.shape[0] != self.n_stages:
            raise ValueError(f"ids must be [R={self.n_stages} slots, B, "
                             f"S_p], got {ids.shape}")
        r_slots, batch, prompt_len = ids.shape
        if new_tokens < 1:
            raise ValueError(f"new_tokens must be >= 1, got {new_tokens}")
        base = 0
        if prefix is not None:
            self.check_prefix(prefix)
            if prompt_len == 0:
                raise ValueError(
                    "prefix reuse needs a non-empty suffix (the span "
                    "produces the first token's logits)")
            base = prefix["len"]
        dec.validate_capacity(self.cfg, self.max_len, base + prompt_len,
                              new_tokens)
        if seeds is None:
            seeds = range(r_slots)
        seeds = list(seeds)
        if len(seeds) != r_slots:
            raise ValueError(f"seeds must have {r_slots} entries, got "
                             f"{len(seeds)}")
        rngs = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
        if prefix is None:
            prefill = self._prefill_prog(r_slots, batch, prompt_len,
                                         temperature, top_k)
            caches = self._zero_caches(r_slots, batch)
            caches, token1, rngs = prefill(self.params, ids, caches, rngs)
        else:
            # suffix prompt pass: ONE span wave at the prefix offset
            caches = self._broadcast_prefix_caches(prefix, r_slots, batch)
            span = self._span_fn(r_slots, batch, prompt_len, "pick_last",
                                 temperature, top_k)
            caches, token1, rngs = span(self.params, ids, caches,
                                        jnp.asarray(base, jnp.int32),
                                        rngs)
        if new_tokens == 1:
            outputs = token1[:, None]                     # [R, 1, B]
        else:
            decode_fn = self._decode_prog(r_slots, batch,
                                          base + prompt_len, new_tokens,
                                          temperature, top_k)
            outputs = decode_fn(self.params, token1, caches, rngs)
        return jnp.concatenate(
            [ids, jnp.transpose(outputs, (0, 2, 1))], axis=2)


class SpmdSpeculativeDecoder:
    """Speculative decoding whose VERIFY runs through the wave pipeline.

    The host `SpeculativeDecoder` verifies one request's span per target
    dispatch; here ONE span-wave program (`_build_span('argmax_all')`)
    verifies ALL R slots' (gamma+1)-token spans in a single compiled
    program per round — every stage verifies a different slot per tick,
    the wave decoder's utilization argument applied to verification.
    The draft is any host-driven `DecodePipeline` over the same
    vocabulary; its R x B rows flatten into one batch, so each draft
    step is ONE dispatch for the whole fleet.

    Greedy-exact per slot: a round accepts the MINIMUM matching prefix
    across ALL slots and rows — the host decoder's batch-safe rule
    extended to the slot axis, which keeps every slot at the SAME cache
    position (the wave's position arithmetic stays pure tick math; no
    per-slot divergence state). Slots that matched deeper re-derive
    those tokens next round; greedy determinism makes the output
    token-identical to `SpmdDecodePipeline.generate(ids, n)` (and hence
    to per-slot host `DecodePipeline.generate`) — tests/
    test_spmd_decode.py. The trade is lower effective acceptance as
    R grows, in exchange for verify spans that ride ICI with zero
    host round trips inside the wave.
    """

    def __init__(self, target: SpmdDecodePipeline, draft, gamma: int = 4):
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        if target.cfg.vocab_size != draft.cfg.vocab_size:
            raise ValueError(
                "draft and target must share a vocabulary: "
                f"{draft.cfg.vocab_size} vs {target.cfg.vocab_size}")
        self.target = target
        self.draft = draft
        self.gamma = gamma
        self.last_acceptance_rate: Optional[float] = None

    def generate(self, ids, new_tokens: int):
        """Greedy-decode all R slots: [R, B, S_p] -> [R, B, S_p + N],
        token-identical to the wave pipeline's own greedy generate."""
        ids = jnp.asarray(ids, jnp.int32)
        tgt = self.target
        if ids.ndim != 3 or ids.shape[0] != tgt.n_stages:
            raise ValueError(f"ids must be [R={tgt.n_stages} slots, B, "
                             f"S_p], got {ids.shape}")
        r_slots, batch, prompt_len = ids.shape
        if new_tokens < 1:
            raise ValueError(f"new_tokens must be >= 1, got {new_tokens}")
        g = self.gamma
        dec.validate_capacity(tgt.cfg, tgt.max_len, prompt_len,
                              new_tokens + g)
        dec.validate_capacity(self.draft.cfg, self.draft.max_len,
                              prompt_len, new_tokens + g)

        # target wave prefill: caches + each slot's first greedy token
        # (the shared program cache — one compile for every generation
        # length and for plain generate too)
        prefill = tgt._prefill_prog(r_slots, batch, prompt_len)
        rngs = jnp.stack([jax.random.PRNGKey(s) for s in range(r_slots)])
        t_caches = tgt._zero_caches(r_slots, batch)
        t_caches, token1, _ = prefill(tgt.params, ids, t_caches, rngs)
        verify = tgt._span_fn(r_slots, batch, g + 1, "argmax_all")

        # draft prefill: slots flatten into the batch axis (one dispatch
        # drafts for the whole fleet)
        flat = ids.reshape(r_slots * batch, prompt_len)
        _, d_caches = self.draft._prefill(flat)

        pending = np.asarray(token1, np.int32)          # [R, B]
        known = [pending]     # committed continuation tokens, [R, B] each
        n_emitted = 1
        t_pos = prompt_len
        d_pos = prompt_len
        proposed = accepted = 0

        while n_emitted < new_tokens:
            # draft catch-up (committed tokens it hasn't seen) + gamma
            # proposals, host-driven on the flattened fleet batch
            catch = np.stack([k.reshape(-1) for k in
                              known[d_pos - prompt_len:]], axis=1)
            d_logits, d_caches = self.draft.extend(
                jnp.asarray(catch), d_caches, d_pos)
            d_pos += catch.shape[1]
            props = [np.asarray(jnp.argmax(
                d_logits[:, -1].astype(jnp.float32), -1), np.int32)]
            for _ in range(g - 1):
                d_logits, d_caches = self.draft.extend(
                    jnp.asarray(props[-1][:, None]), d_caches, d_pos)
                props.append(np.asarray(jnp.argmax(
                    d_logits[:, -1].astype(jnp.float32), -1), np.int32))
                d_pos += 1

            # ONE span wave verifies every slot's pending + proposals
            spans = np.concatenate(
                [pending.reshape(r_slots, batch, 1)]
                + [p.reshape(r_slots, batch, 1) for p in props], axis=2)
            t_caches, targets, _ = verify(
                tgt.params, jnp.asarray(spans), t_caches,
                jnp.asarray(t_pos, jnp.int32), rngs)
            targets = np.asarray(targets, np.int32)     # [R, g+1, B]

            # accept the minimum matching prefix across ALL slots + rows
            a = 0
            while a < g and bool(np.all(
                    props[a].reshape(r_slots, batch) == targets[:, a])):
                a += 1
            proposed += g
            accepted += a
            known.extend([props[k].reshape(r_slots, batch)
                          for k in range(a)] + [targets[:, a]])
            n_emitted += a + 1
            pending = targets[:, a]
            t_pos += a + 1
            d_pos = t_pos - 1 if a == g else t_pos

        self.last_acceptance_rate = accepted / proposed if proposed \
            else None
        gen = jnp.asarray(np.stack(known[:new_tokens], axis=2))
        return jnp.concatenate([ids, gen], axis=2)
