"""Granite 4.0-H (`model_type` granitemoehybrid; granite-4.0-h-micro, the
dense member: `num_local_experts` 0): blocks of a mixer THEN a SwiGLU, most
mixers Mamba-2 and a few plain attention, the Granite line's four constants
on the embedding, the residual branches, the attention's scores and the
logits, and the head the embedding.

The model, `x` [B, S, D], plain RMSNorm, no bias but the convolution's:
  h0 = scale_emb * embed[ids]
  h  = x + r * Mixer(rms(x; input_layernorm))
  x' = h + r * W_out (silu(W_gate u) * (W_up u)),  u = rms(h; post_attention_layernorm)
  logits = (rms(h_last; norm) @ embed^T) / logits_scaling
with `r` = `cfg.residual_multiplier` on BOTH branches. `cfg.layer_types[i]`
("mamba" | "attention") names block i's mixer, which is its kind
(`block_kind`): the published pattern puts an attention block at 5, 15, 25
and 35 of 40, so a whole model's stage is nine runs (5, 1, 9, 1, 9, 1, 9, 1
and 4 blocks; models/shard.py `BlockRuns`).

**The mixers** are `models/mamba2.py`'s, which the nemotron_h family
shares. Mamba-2 here has ONE group: all `H` = 64 heads of 64 read the same
`B_t` and `C_t` and the gated norm is a plain RMSNorm over the 4,096 lanes.
The attention (32 query / 8 KV heads of 64, `position_embedding_type`
"nope": no rotation) takes its scores times `cfg.attention_multiplier`
(0.015625 = 1/64, NOT `64**-0.5`).

**The feed-forward part** is the published `shared_mlp` (with no experts it
is the only one): the checkpoint's fused `input_linear` [2 F, D] is gate
(rows 0 .. F - 1) then up, split by the loader; `decoder.dense_ffn`.

**Cache** (`cache_leaves`, `mamba2.cache_leaves`): the attention blocks own
`k`, `v` `[4, B, T, 512]`, the Mamba-2 blocks `ssm_state` `[36, B, 64, 64,
128]` (2.10 MB a request a layer: 4.83 GB at 64 rows, 30% of a v5e chip,
which the decode driver writes in place and a step's kernel updates where
it lies, `parallel/decode.py::WHOLE_IN_PLACE_BYTES`) and `ssm_conv`.

**Precision** as `models/mamba2.py` says: weights as stored, activations,
state, tail, keys and values float32. The model has no discrete choice (no
router, no top-k), so nothing amplifies a rounding.

**Prefill** runs in spans of `cfg.prefill_chunk` positions through the
decode-shaped stage program; at 64 rows a span of 64 keeps the widest
three-pass product (the SwiGLU's, 8,192 lanes) under
`decoder.PRODUCT_BYTES` without chunks of rows, and a span's chunk of the
Mamba-2 scan is then the span.

**The head is the embedding**: one table in the checkpoint, read once and,
in a stage that holds both ends, ONE array on the device, the embedding's
(`_assemble`).

Refused by name: the forward path (`sublayer`), tp, sp and ep meshes, the
int8 cache, `--kv-pages`, the SPMD wave decoder and speculative verify (a
rejected draft would need the state of an earlier position).

Weight format (`model.layers.N.`): `{input_layernorm,
post_attention_layernorm}.weight`; `mamba.{in_proj,out_proj}.weight`,
`mamba.conv1d.{weight,bias}`, `mamba.{A_log,D,dt_bias}`, `mamba.norm.weight`
in a Mamba-2 block; `self_attn.{q,k,v,o}_proj.weight` in an attention
block; `shared_mlp.{input_linear,output_linear}.weight`;
`model.embed_tokens.weight`, `model.norm.weight`; no `lm_head.weight`.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig, decoder, mamba2
from .layers import TransformerConfig, rms_norm
from .shard import FamilySpec

# what a block step counts into the cache's `stats` leaf, in this order (no
# expert layer, so no expert counters)
STATS = mamba2.STATS


def prefill_span(cfg: TransformerConfig) -> int:
    return cfg.prefill_chunk


def block_kind(cfg: TransformerConfig, block_id: int) -> str:
    return cfg.layer_types[block_id]


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """The cache's leaves (module docstring, Cache), with the kind of block
    that owns each."""
    return dict(mamba2.cache_leaves(cfg), stats=jax.ShapeDtypeStruct(
        (len(STATS),), jnp.int32))


def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """Cached block (the decode driver's `_block_step` contract) of either
    kind: the mixer, then the SwiGLU, each after its norm and each times
    the residual multiplier. The rows of `x` sit at [pos, pos + S). A
    Mamba-2 block takes its state and its convolution's tail from the cache
    and records what they are after the span (`mamba2.mamba_block`); an
    attention block attends the cached window below `pos` and its own rows
    and records their keys and values for `write_rows`."""
    eps, r = cfg.layer_norm_eps, cfg.residual_multiplier
    normed = rms_norm(p["ln_before"], x, eps)
    if "in_proj" in p:
        mixed, bcache, rows, counts = mamba2.mamba_block(p, normed, bcache,
                                                         cfg, prefill)
    else:
        mixed, bcache, fused = mamba2.attention(
            p, normed, bcache, pos, cfg, prefill, read_len,
            scale=cfg.attention_multiplier)
        rows, counts = dict(bcache.rows), [0, 0, 0, fused, 0]
    h = x + r * mixed
    delta = decoder.dense_ffn(p["mlp"], rms_norm(p["ln_after"], h, eps))
    rows["stats"] = jnp.stack([jnp.asarray(c, jnp.int32) for c in counts])
    return h + r * delta, bcache._replace(rows=rows)


# positions live in the Mamba-2 layers' state and convolution
FAMILY = FamilySpec(name="granite_hybrid", cached_block_step=cached_block_step,
                    **decoder.token_hooks("granite_hybrid",
                                          mamba2.ACTIVATIONS, rms_norm),
                    decoder_model=True, position_dependent_attention=True,
                    cache_leaves=cache_leaves, prefill_span=prefill_span,
                    stats_names=STATS, block_kind=block_kind)


# -- loading -------------------------------------------------------------------

def _assemble(cfg: TransformerConfig, shard_config: ShardConfig, get,
              dtype) -> Dict:
    """Shard params from `get(key, shape)`, a tensor of the published
    scheme (module docstring; `decoder.loader`, `assemble_shard`). `A_log`,
    `D`, `dt_bias` and the trunk's two `factor`s (`decoder.token_hooks`)
    stay float32. A stage that embeds and has the head holds the table
    once: the head is given the embedding's array after it is placed."""
    d, width = cfg.hidden_size, cfg.intermediate_size
    both_ends = shard_config.is_first and shard_config.is_last

    def scale(key, n):
        return {"scale": get(key, (n,))}

    def table():
        return get("model.embed_tokens.weight", (cfg.vocab_size, d))

    def get_embed() -> Dict:
        return {"wte": table(), "factor": np.float32(cfg.scale_emb)}

    def get_block(block_id: int, subs: tuple) -> Dict:
        decoder.whole_blocks("granite_hybrid", subs)
        root = f"model.layers.{block_id}."
        if block_kind(cfg, block_id) == "mamba":
            p = mamba2.mamba_leaves(get, root + "mamba.", cfg)
        else:
            p = mamba2.attention_leaves(get, root + "self_attn.", cfg)
        fused = get(root + "shared_mlp.input_linear.weight", (2 * width, d))
        p.update(
            ln_before=scale(root + "input_layernorm.weight", d),
            ln_after=scale(root + "post_attention_layernorm.weight", d),
            mlp={"gate": fused[:width], "up": fused[width:],
                 "down": get(root + "shared_mlp.output_linear.weight",
                             (d, width))})
        return p

    def get_final() -> Dict:
        final = {"ln": scale("model.norm.weight", d),
                 "factor": np.float32(1.0 / cfg.logits_scaling)}
        if not both_ends:
            final["head"] = {"w": table()}
        return final

    params = decoder.assemble_shard(
        shard_config, get_embed, get_block, get_final, dtype,
        kind=lambda block_id: block_kind(cfg, block_id),
        float32=mamba2.FLOAT32 + (("factor",),))
    if both_ends:
        params["final"]["head"] = {"w": params["embeddings"]["wte"]}
    return params


def _undrawn(key: str, shape: tuple):
    """What `init_params` does not draw: the norms' weights ones, and the
    Mamba-2 mixers' `D` and decays (`mamba2.undrawn`)."""
    if key.endswith(("norm.weight", "layernorm.weight")):
        return np.ones(shape, np.float32)
    return mamba2.undrawn(key, shape)


load_params, init_params = decoder.loader(_assemble, _undrawn)
