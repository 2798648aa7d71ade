"""Mellum (`model_type` mellum, JetBrains' Mellum2): laguna's window-and-full
block with other numbers and fewer parts. Periods of four, three layers that
attend the last `sliding_window` positions and one that attends every
position under YaRN; ONE count of query heads for both kinds; the rotation
over the whole head in both (`partial_rotary_factor` 1), the window layers'
plain at the same base; NO gate a head; every FFN routed experts by a
renormalised softmax, no scaling factor, NO shared expert, NO leading dense
layer; embedding and head untied.

The block, its cache's leaves (rows a position in the full layers, a ring
in the window layers), its prompt pass in spans and its row step are
`models/laguna.py`'s, which reads each of those differences off the
configuration and a block's leaves: this module is the family's name and
its key map and holds no code of the block (ROADMAP D1).

Weight format: Qwen3-MoE's state dict (`model.layers.N.{input_layernorm,
post_attention_layernorm}.weight`, `.self_attn.{q_proj,k_proj,v_proj,
o_proj}.weight`, `.self_attn.{q_norm,k_norm}.weight`, `.mlp.gate.weight`,
`.mlp.experts.E.{gate_proj,up_proj,down_proj}.weight`; `model.embed_tokens`,
`model.norm`, `lm_head`): laguna's loader without `g_proj`, `mlp.
shared_expert*` and the dense layer's `mlp.*_proj`, none of which the
configuration asks it for. The "MTP head" of the model's card has no key in
the published configuration and is left out, as qwen3_next's and
nemotron_h's `mtp.*` are.
"""
from __future__ import annotations

import dataclasses

from . import laguna

FAMILY = dataclasses.replace(
    laguna.FAMILY, name="mellum",
    **laguna.decoder.token_hooks("mellum", laguna.ACTIVATIONS,
                                 laguna.rms_norm))
load_params, init_params = laguna.load_params, laguna.init_params
