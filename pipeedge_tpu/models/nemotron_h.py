"""Nemotron-H (`model_type` nemotron_h; Nemotron-3-Super): a trunk of layers
that are ONE sublayer each, a Mamba-2 mixer, a plain attention or an expert
layer whose routed experts work in a latent narrower than the model.

The block, `x` [B, S, D], plain RMSNorm, no bias but the convolution's:
  x' = x + Mixer(rms(x; norm))
with no second norm and no FFN behind a mixer: `cfg.layer_types[i]` ("mamba"
| "attention" | "experts", the letters M, * and E of the published
`hybrid_override_pattern`) names block i's one sublayer, which is its kind
(`block_kind`). The published pattern alternates, so a stage's blocks are
runs of one (models/shard.py `BlockRuns`): eleven runs in `MEMEMEM*EME`.
A block still counts four in a `-pt` partition's sublayer numbers, as every
family's does; the family takes whole blocks.

**Mamba-2 and the attention** are `models/mamba2.py`'s, which the
granite_hybrid family shares: the mixer in both forms (`mamba`, `ssm_step`,
`ssm_chunked`), the state kernel's choice (`state_kernel_mode`), the
attention without rotation (causal softmax at `Dh**-0.5`) and the cache's
leaves. Here `H` = 128 heads of 64 in `G` = 8 groups of 16.

**Latent experts.** The router reads the token `u` (sigmoid scores over all
`n_experts`, the `num_experts_per_tok` largest of score + correction bias,
the kept scores over their sum, times `routed_scaling_factor`); the routed
experts read `c = latent.down u`, `cfg.moe_latent_size` wide, and are
`down(relu(up c)^2)`, two matrices; `latent.up` takes their weighted sum back
up (`parallel/expert.py::topk_ffn_delta`, which is told all this by the
leaves it is handed), and the shared expert, `down(relu(up u)^2)` on the full
width, is added here (`decoder.dense_ffn`). The up projection is linear, so
the shares of a deployment's chips add up and the shared expert counts once.

**Cache: a leaf that is a sixth of the chip** (`cache_leaves`,
`mamba2.cache_leaves`). The one attention kind owns `k`, `v`; the Mamba-2
blocks own `ssm_state` (4.19 MB a request a layer at the published sizes:
537 MB a layer at 128 rows, which the decode driver writes in place and a
step's kernel updates where it lies) and `ssm_conv`. The expert blocks own
none.

**Precision.** Weights as stored (bfloat16; `A_log`, `D`, `dt_bias` and the
router's bias float32); activations, state, tail, keys and values float32:
products with weights through `exact_dot`, `C B^T`, the state's products and
q.k at `HIGHEST`, a step's state update float32 multiplications and sums on
the vector unit, in the kernel as in the jnp step (three products and a sum
an element, the decay `exp_ulp`'s, the sum over N in the lanes' order;
nothing of it through the matrix unit or a bfloat16 pass); the router's
scores in float32 (its top-22 of 512 is a discrete choice a narrower
computation makes differently).

**Prefill** runs in spans of `cfg.prefill_chunk` positions (a multiple of the
chunk) through the decode-shaped stage program; a span's Mamba-2 mixer runs
the batch in groups of rows whose projections stay under
`decoder.PRODUCT_BYTES` (`mamba`).

Refused by name: the forward path (`sublayer`), tp, sp and ep meshes, the
int8 cache, `--kv-pages`, the SPMD wave decoder and speculative verify (a
rejected draft would need the state of an earlier position).

Weight format (`backbone.layers.N.`): `norm.weight`; `mixer.{in_proj,
out_proj}.weight`, `mixer.conv1d.{weight,bias}`, `mixer.{A_log,D,dt_bias}`,
`mixer.norm.weight` in a Mamba-2 layer; `mixer.{q,k,v,o}_proj.weight` in an
attention layer; `mixer.gate.{weight,e_score_correction_bias}`,
`mixer.{fc1,fc2}_latent_proj.weight`, `mixer.experts.E.{up,down}_proj.
weight`, `mixer.shared_experts.{up,down}_proj.weight` in an expert layer;
`backbone.embeddings.weight`, `backbone.norm_f.weight`, `lm_head.weight`
(untied). The multi-token-prediction module (`mtp.*`) is no part of this.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig, decoder, mamba2
from .layers import TransformerConfig, rms_norm
# the mixer's forms by the names the family's tests know them under
from .mamba2 import (ACTIVATIONS, conv_channels, mamba, ssm_chunked,  # noqa: F401
                     ssm_step)
from .shard import FamilySpec

# what a block step counts into the cache's `stats` leaf, in this order: the
# expert layers' five, then what the Mamba-2 and attention layers count
STATS = decoder.MOE_STATS + mamba2.STATS


def prefill_span(cfg: TransformerConfig) -> int:
    return cfg.prefill_chunk


def block_kind(cfg: TransformerConfig, block_id: int) -> str:
    return cfg.layer_types[block_id]


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """The cache's leaves (module docstring, Cache), with the kind of block
    that owns each."""
    return dict(mamba2.cache_leaves(cfg), stats=jax.ShapeDtypeStruct(
        (len(STATS),), jnp.int32))


# -- the family's hooks --------------------------------------------------------

def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """Cached block (the decode driver's `_block_step` contract) of any of
    the three kinds: one sublayer after one norm. The rows of `x` sit at
    [pos, pos + S). A Mamba-2 block takes its state and its convolution's
    tail from the cache (a prefill, at `pos` 0: zeros) and records what they
    are after the span, which takes their place (a step whose state the
    kernel has updated in the stack, `state_kernel_mode`, records no state:
    it hands back the cache with that stack); an attention block attends
    the cached window below `pos` and its own rows and records their keys
    and values for `write_rows`; an expert block touches no leaf."""
    b, s, _ = x.shape
    normed = rms_norm(p["ln"], x, cfg.layer_norm_eps)
    moe, counts = jnp.zeros(5, jnp.int32), [0, 0, 0, 0, 0]
    rows = {}
    if "in_proj" in p:
        mixed, bcache, rows, counts = mamba2.mamba_block(p, normed, bcache,
                                                         cfg, prefill)
    elif "q" in p:
        mixed, bcache, fused = mamba2.attention(p, normed, bcache, pos, cfg,
                                                prefill, read_len)
        rows, counts = dict(bcache.rows), [0, 0, 0, fused, 0]
    else:
        mixed, moe = decoder.routed_experts(
            {name: leaf for name, leaf in p.items() if name != "shared"},
            normed, cfg)
        # in chunks of rows: a span's 16 k tokens over its 5,376 lanes
        mixed = mixed + decoder.dense_ffn(p["shared"], normed, cfg.expert_act)
        moe = jnp.concatenate([moe.astype(jnp.int32), jnp.ones(1, jnp.int32)])
    rows["stats"] = jnp.concatenate(
        [moe, jnp.stack([jnp.asarray(c, jnp.int32) for c in counts])])
    return x + mixed, bcache._replace(rows=rows)


# positions live in the Mamba-2 layers' state and convolution
FAMILY = FamilySpec(name="nemotron_h", cached_block_step=cached_block_step,
                    **decoder.token_hooks("nemotron_h", ACTIVATIONS, rms_norm),
                    decoder_model=True, position_dependent_attention=True,
                    cache_leaves=cache_leaves, prefill_span=prefill_span,
                    whole_leaves=("experts",), stats_names=STATS,
                    block_kind=block_kind)


# -- loading -------------------------------------------------------------------

def _assemble(cfg: TransformerConfig, shard_config: ShardConfig, get,
              dtype) -> Dict:
    """Shard params from `get(key, shape)`, a tensor of the published
    scheme (module docstring; `decoder.loader`, `assemble_shard`). `A_log`,
    `D`, `dt_bias` and the router's bias stay float32, as published."""
    d = cfg.hidden_size
    latent, width = cfg.moe_latent_size, cfg.moe_intermediate_size
    first, count = cfg.held_experts or (0, cfg.n_experts)

    def scale(key, n):
        return {"scale": get(key, (n,))}

    def mlp(root, width, d_in):     # down(relu(up x)^2)
        return {"up": get(root + "up_proj.weight", (width, d_in)),
                "down": get(root + "down_proj.weight", (d_in, width))}

    def get_embed() -> Dict:
        return {"wte": get("backbone.embeddings.weight", (cfg.vocab_size, d))}

    def get_block(block_id: int, subs: tuple) -> Dict:
        decoder.whole_blocks("nemotron_h", subs)
        root = f"backbone.layers.{block_id}."
        mix, kind = root + "mixer.", block_kind(cfg, block_id)
        p = {"ln": scale(root + "norm.weight", d)}
        if kind == "mamba":
            p.update(mamba2.mamba_leaves(get, mix, cfg))
        elif kind == "attention":
            p.update(mamba2.attention_leaves(get, mix, cfg))
        else:
            held = [mlp(f"{mix}experts.{e}.", width, latent)
                    for e in range(first, first + count)]
            p.update(
                router={"w": get(mix + "gate.weight", (cfg.n_experts, d)).T,
                        "bias": get(mix + "gate.e_score_correction_bias",
                                    (cfg.n_experts,))},
                latent={"down": get(mix + "fc1_latent_proj.weight",
                                    (latent, d)),
                        "up": get(mix + "fc2_latent_proj.weight",
                                  (d, latent))},
                experts={name: decoder.stack([one[name] for one in held])
                         for name in ("up", "down")},
                shared=mlp(mix + "shared_experts.", cfg.shared_expert_width,
                           d))
        return p

    def get_final() -> Dict:
        return {"ln": scale("backbone.norm_f.weight", d),
                "head": {"w": get("lm_head.weight", (cfg.vocab_size, d))}}

    return decoder.assemble_shard(
        shard_config, get_embed, get_block, get_final, dtype,
        kind=lambda block_id: block_kind(cfg, block_id),
        float32=(("router", "bias"),) + mamba2.FLOAT32)


def _undrawn(key: str, shape: tuple):
    """What `init_params` does not draw: the norms' weights ones, and the
    Mamba-2 mixers' `D` and decays (`mamba2.undrawn`)."""
    if key.endswith(("norm.weight", "norm_f.weight")):
        return np.ones(shape, np.float32)
    return mamba2.undrawn(key, shape)


load_params, init_params = decoder.loader(
    _assemble, _undrawn,
    vocabulary=("backbone.embeddings.weight", "lm_head.weight"))
