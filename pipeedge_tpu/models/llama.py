"""LLaMA model family: RoPE/RMSNorm/SwiGLU/GQA decoder shards.

NEW capability beyond the reference (whose model list is encoder-only,
/root/reference/model_cfg.py:24-43) and beyond the GPT-2 family: the
modern decoder shape — rotary position embeddings instead of a learned
position table, RMSNorm instead of LayerNorm, a gated SwiGLU MLP, and
grouped-query attention (fewer K/V heads than query heads — the KV-cache
memory lever serving stacks rely on). It slots into the same 4-sublayer
cut discipline as every other family:
  sub 0: rms_norm -> RoPE'd GQA self-attention   payload becomes (ctx, residual)
  sub 1: attention output proj + residual        payload becomes hidden
  sub 2: rms_norm -> silu(gate) * up             payload becomes (mlp_h, residual)
  sub 3: MLP-down + residual                     payload becomes hidden
First shard: token embedding (no position table — positions live in the
rotation). Last shard: final RMSNorm + LM head.

KV-cache decoding: the family supplies its own cached block step
(`cached_block_step`) and single-token embed (`decode_embed`) through the
FamilySpec hooks, so `DecodePipeline` / the continuous batcher / the SPMD
wave decoder drive LLaMA unchanged. The cache stores POST-RoPE K at the
GQA head count ([blocks, B, T, kv_heads * Dh] — `cfg.kv_heads` sizes it),
and each step rotates only the new token's q/k at its position.

Weight format: HF `LlamaForCausalLM` state dict (`model.`-prefixed
`nn.Linear` kernels, stored [out, in] -> transposed to [in, out] at load;
no biases — zero vectors keep the {w, b} pytree shape shared with the
other families). The FORWARD-pipeline sequence-parallel attention
override is refused (those cores compute projections chunk-locally with
no global RoPE offset); the decode subsystem's sp PREFILL is supported
via `sp_prefill_block_step`, which pre-rotates q/k at global chunk
positions before the chunk-local core.
"""
from __future__ import annotations

from typing import Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig
from .layers import TransformerConfig, dense, rms_norm, rope_rotate
from .shard import FamilySpec, build_shard_params
from .stage_cache import attend, attend_rows, cache_update_and_read

SUBLAYER_PARAMS = {
    0: ("ln_before", "q", "k", "v"),
    1: ("attn_out",),
    2: ("ln_after", "mlp_gate", "mlp_up"),
    3: ("mlp_down",),
}


def _split_heads(y: jax.Array, n_heads: int) -> jax.Array:
    b, s, _ = y.shape
    return y.reshape(b, s, n_heads, -1)


def _window_keep(keep: jax.Array, q_pos, cfg: TransformerConfig):
    """Intersect a keep mask [S_q, S_k] with the sliding window: position
    q attends to k in (q - window, q] (Mistral semantics — the last
    `sliding_window` positions including itself). `q_pos` gives each
    query row's absolute position; no-op when the window is off."""
    if not cfg.sliding_window:
        return keep
    k_pos = jax.lax.broadcasted_iota(jnp.int32, keep.shape, 1)
    return keep & (k_pos > q_pos - cfg.sliding_window)


def _gqa_attend(q, k, v, cfg: TransformerConfig, keep=None) -> jax.Array:
    """softmax(QK^T)V over grouped kv heads; `keep` optionally masks key
    positions (the decode path: k, v and keep are then the parts
    `cache_update_and_read` returns, sliding window included), else
    causal (+ window). The stage cache's `attend` is the masked
    softmax and the grouping both (a query head reads its group's kv head;
    nothing is repeated up to the query heads) — ONE copy of the attention
    numerics for both consumers."""
    if keep is None:                 # full forward: causal over [S, S]
        s_q, s_k = q.shape[1], k.shape[1]
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        keep = _window_keep(k_pos <= q_pos, q_pos, cfg)
    return attend(q, k, v, keep, cfg)


def _qkv_rope(p: Dict, normed: jax.Array, cfg: TransformerConfig, pos):
    """Project + RoPE-rotate q/k (v unrotated) at positions `pos` [S]."""
    q = _split_heads(dense(p["q"], normed), cfg.num_attention_heads)
    k = _split_heads(dense(p["k"], normed), cfg.kv_heads)
    v = _split_heads(dense(p["v"], normed), cfg.kv_heads)
    return (rope_rotate(q, pos, cfg.rope_theta),
            rope_rotate(k, pos, cfg.rope_theta), v)


def embed(p: Dict, input_ids: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Token embedding only — positions live in the rotation."""
    return jnp.take(p["wte"], input_ids, axis=0)


def sublayer(p: Dict, sub: int, data, cfg: TransformerConfig,
             attention_fn=None):
    """One of the 4 schedulable sublayers (pre-RMSNorm block, RoPE GQA)."""
    if attention_fn is not None:
        raise NotImplementedError(
            "llama attention cores are position-dependent (RoPE); the "
            "sequence-parallel attention override is not supported")
    if sub == 0:
        normed = rms_norm(p["ln_before"], data, cfg.layer_norm_eps)
        pos = jnp.arange(normed.shape[1])
        q, k, v = _qkv_rope(p, normed, cfg, pos)
        return (_gqa_attend(q, k, v, cfg), data)
    if sub == 1:
        ctx, skip = data
        return dense(p["attn_out"], ctx) + skip
    if sub == 2:
        normed = rms_norm(p["ln_after"], data, cfg.layer_norm_eps)
        gated = jax.nn.silu(dense(p["mlp_gate"], normed).astype(
            jnp.float32)).astype(normed.dtype)
        return (gated * dense(p["mlp_up"], normed), data)
    if sub == 3:
        mlp_h, skip = data
        return dense(p["mlp_down"], mlp_h) + skip
    raise ValueError(f"sublayer must be 0..3, got {sub}")


def finalize(p: Dict, hidden: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Final RMSNorm + LM head -> [B, S, vocab] logits."""
    return dense(p["head"], rms_norm(p["ln"], hidden, cfg.layer_norm_eps))


def decode_embed(pe: Dict, tok: jax.Array, pos) -> jax.Array:
    """Single decode-step token embed [B, 1, D]: wte row only (RoPE puts
    the position into the attention rotation, not the embedding)."""
    return jnp.take(pe["wte"], tok.reshape(-1), axis=0)[:, None]


def span_embed(pe: Dict, tok: jax.Array, pos) -> jax.Array:
    """K-token span embed [B, K] -> [B, K, D] (speculative verify):
    wte rows only — positions enter via RoPE in the attention."""
    return jnp.take(pe["wte"], tok, axis=0)


def _block_tail(p: Dict, x, ctx, cfg: TransformerConfig):
    """Post-attention half of a llama block (output proj + residual,
    RMSNorm, SwiGLU + residual) — ONE copy shared by the cached decode
    step and the sp prefill so their numerics cannot diverge."""
    h = dense(p["attn_out"], ctx) + x
    normed = rms_norm(p["ln_after"], h, cfg.layer_norm_eps)
    gated = jax.nn.silu(dense(p["mlp_gate"], normed).astype(
        jnp.float32)).astype(normed.dtype)
    return dense(p["mlp_down"], gated * dense(p["mlp_up"], normed)) + h


def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """KV-cached llama block (the decode driver's `_block_step` contract):
    prefill writes the whole prompt's POST-RoPE K and V at [0, S); a decode
    step rotates the single new token at `pos` and
    attends over the masked cache window (truncated to the static
    `read_len` bucket when the pipeline passes one — cache positions are
    absolute from 0, so the window mask anchors unchanged)."""
    normed = rms_norm(p["ln_before"], x, cfg.layer_norm_eps)
    s = normed.shape[1]
    # pos + offset covers prefill (pos=0), decode (s=1), and span steps
    pos_ids = jnp.asarray(pos) + jnp.arange(s)
    q, k_new, v_new = _qkv_rope(p, normed, cfg, pos_ids)
    k, v, keep, bcache = cache_update_and_read(
        bcache, k_new, v_new, pos, prefill, s, q.dtype, read_len=read_len,
        window=cfg.sliding_window)
    ctx = _gqa_attend(q, k, v, cfg, keep=keep)
    return _block_tail(p, x, ctx, cfg), bcache


def tp_cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                         prefill: bool, axis: str, read_len=None):
    """Tensor-parallel KV-cached llama block under `shard_map`: the
    forward Megatron body (parallel/tensor.py `_tp_llama_block_local` —
    ONE copy of the projection/psum/SwiGLU numerics) with the attention
    core swapped for a cache-attend over the head-sharded GQA cache
    slice. Requires heads AND kv_heads divisible by the tp degree.
    `read_len`: static bucketed attend window (position axis unsharded)."""
    from ..parallel.tensor import _tp_llama_block_local

    def cache_attend(q, k_new, v_new):
        nonlocal bcache
        k, v, keep, bcache = cache_update_and_read(
            bcache, k_new, v_new, pos, prefill, x.shape[1], q.dtype,
            read_len=read_len, window=cfg.sliding_window)
        return _gqa_attend(q, k, v, cfg, keep=keep)

    pos_ids = jnp.asarray(pos) + jnp.arange(x.shape[1])
    y = _tp_llama_block_local(p, x, cfg, axis, qkv_to_ctx=cache_attend,
                              pos_ids=pos_ids)
    return y, bcache


def tp_finalize(pf: Dict, hidden, cfg: TransformerConfig, axis: str):
    """Vocab-sharded LM head under tp (shared helper, RMS norm)."""
    from ..parallel.decode import tp_vocab_head_finalize
    return tp_vocab_head_finalize(pf, hidden, cfg, axis, norm_fn=rms_norm)


def sp_prefill_block_step(p: Dict, x, bcache, cfg: TransformerConfig,
                          axis: str, core, cache_gather):
    """Sequence-parallel llama prefill block: RoPE is applied at GLOBAL
    chunk positions (chunk_start + local offset) BEFORE the sp core, so
    the rotation carries the position information and the chunk-local
    ring/Ulysses core stays position-agnostic — exactly why the plain
    attention-override path refuses RoPE families but this hook is sound.
    The sp cores are GQA-aware (parallel/sequence.py): unrepeated K/V
    ride the ring ppermutes / all-to-alls and repeat only inside the
    local attend, so the inter-chip traffic keeps GQA's kv_heads/heads
    size advantage; the cache likewise gathers the UNREPEATED post-RoPE
    rows the per-token decode steps read. Sliding-window (Mistral)
    configs need no handling here: make_sp_prefill_fn binds
    cfg.sliding_window into `core`, and the cache gathers the full
    post-RoPE rows — the per-token decode steps apply their own window
    mask over the cache (_window_keep)."""
    normed = rms_norm(p["ln_before"], x, cfg.layer_norm_eps)
    b, s_local, _ = x.shape
    idx = jax.lax.axis_index(axis)
    pos = idx * s_local + jnp.arange(s_local)
    q, k_new, v_new = _qkv_rope(p, normed, cfg, pos)
    ctx = core(q, k_new, v_new, axis, causal=True)
    return (_block_tail(p, x, ctx.reshape(b, s_local, -1), cfg),
            cache_gather(bcache, k_new, v_new))


def rows_block_step(p: Dict, x, bcache, at, cfg: TransformerConfig,
                    block: int):
    """`cached_block_step` for one token a row, row r at `at.pos[r]` (the
    served executor's step, parallel/decode_rows.py): each row's q and k
    are rotated at its own position (the rows taken as one sequence of R
    positions, which is what `rope_rotate` turns) and attend their own
    slot of the cache."""
    normed = rms_norm(p["ln_before"], x, cfg.layer_norm_eps)
    rows = x.shape[0]
    q, k_new, v_new = (
        y.reshape((rows, 1) + y.shape[2:])
        for y in _qkv_rope(p, normed.reshape(1, rows, -1), cfg, at.pos))
    ctx, bcache = attend_rows(bcache, q, k_new, v_new, at, block, cfg,
                              window=cfg.sliding_window)
    return _block_tail(p, x, ctx, cfg), bcache


FAMILY = FamilySpec(name="llama", embed=embed, sublayer=sublayer,
                    finalize=finalize, cached_block_step=cached_block_step,
                    decode_embed=decode_embed, span_embed=span_embed,
                    decoder_model=True, position_dependent_attention=True,
                    tp_cached_block_step=tp_cached_block_step,
                    tp_finalize=tp_finalize,
                    sp_prefill_block_step=sp_prefill_block_step,
                    rows_block_step=rows_block_step)


def _a(x, dtype):
    return jnp.asarray(np.asarray(x), dtype=dtype)


def _lin(sd, key, dtype):
    """HF nn.Linear kernel [out, in] -> {w [in, out], b zeros}."""
    w = np.asarray(sd[key])
    return {"w": _a(w.T, dtype), "b": jnp.zeros((w.shape[0],), dtype)}


def load_params(cfg: TransformerConfig, shard_config: ShardConfig,
                weights: Mapping, dtype=jnp.float32) -> Dict:
    """Build shard params from an HF `LlamaForCausalLM` state-dict npz."""
    keys = set(weights.keys())
    sd = {k.removeprefix("model."): weights[k] for k in keys
          if k.startswith("model.")}
    if "lm_head.weight" in keys:
        sd["lm_head.weight"] = weights["lm_head.weight"]

    def get_embed() -> Dict:
        return {"wte": _a(sd["embed_tokens.weight"], dtype)}

    def get_block(block_id: int, subs: tuple) -> Dict:
        root = f"layers.{block_id}."
        p: Dict = {}
        if 0 in subs:
            p["ln_before"] = {
                "scale": _a(sd[root + "input_layernorm.weight"], dtype)}
            p["q"] = _lin(sd, root + "self_attn.q_proj.weight", dtype)
            p["k"] = _lin(sd, root + "self_attn.k_proj.weight", dtype)
            p["v"] = _lin(sd, root + "self_attn.v_proj.weight", dtype)
        if 1 in subs:
            p["attn_out"] = _lin(sd, root + "self_attn.o_proj.weight", dtype)
        if 2 in subs:
            p["ln_after"] = {
                "scale": _a(sd[root + "post_attention_layernorm.weight"],
                            dtype)}
            p["mlp_gate"] = _lin(sd, root + "mlp.gate_proj.weight", dtype)
            p["mlp_up"] = _lin(sd, root + "mlp.up_proj.weight", dtype)
        if 3 in subs:
            p["mlp_down"] = _lin(sd, root + "mlp.down_proj.weight", dtype)
        return p

    def get_final() -> Dict:
        head = sd.get("lm_head.weight", sd["embed_tokens.weight"])  # tied
        return {"ln": {"scale": _a(sd["norm.weight"], dtype)},
                "head": {"w": _a(np.asarray(head).T, dtype),
                         "b": jnp.zeros((np.asarray(head).shape[0],),
                                        dtype)}}

    return build_shard_params(shard_config, get_embed, get_block, get_final)


def init_params(cfg: TransformerConfig, shard_config: ShardConfig,
                seed: int = 0, dtype=jnp.float32) -> Dict:
    """Random shard params with the same pytree structure as `load_params`."""
    rng = np.random.default_rng(seed)
    d, it = cfg.hidden_size, cfg.intermediate_size
    kv_d = cfg.kv_heads * cfg.head_dim

    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.02, size=shape), dtype=dtype)

    def lin(n_in, n_out):
        return {"w": mat(n_in, n_out), "b": jnp.zeros((n_out,), dtype)}

    def rms():
        return {"scale": jnp.ones((d,), dtype)}

    def get_embed() -> Dict:
        return {"wte": mat(cfg.vocab_size, d)}

    def get_block(block_id: int, subs: tuple) -> Dict:
        p: Dict = {}
        if 0 in subs:
            p["ln_before"] = rms()
            p["q"] = lin(d, d)
            p["k"] = lin(d, kv_d)
            p["v"] = lin(d, kv_d)
        if 1 in subs:
            p["attn_out"] = lin(d, d)
        if 2 in subs:
            p["ln_after"] = rms()
            p["mlp_gate"] = lin(d, it)
            p["mlp_up"] = lin(d, it)
        if 3 in subs:
            p["mlp_down"] = lin(it, d)
        return p

    def get_final() -> Dict:
        return {"ln": rms(), "head": lin(d, cfg.vocab_size)}

    return build_shard_params(shard_config, get_embed, get_block, get_final)
