"""Pure-function transformer building blocks (MXU-friendly, dtype-flexible).

These replace the reference's use of HuggingFace torch modules
(ViTSelfAttention/ViTIntermediate/... — reference vit.py:12-14, bert.py:10-12)
with jittable functions over parameter pytrees. Matmuls accumulate in float32
via `preferred_element_type` so bfloat16 parameters/activations keep MXU
throughput without losing accumulation precision.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..telemetry import metrics as prom


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Static model hyperparameters (stands in for HF `AutoConfig`, which the
    reference fetches over the network — model_cfg.py:57-66; here configs are
    local constants so the framework runs with zero egress)."""
    model_type: str              # the family: 'vit' | 'bert' | 'deit' |
    #                              'gpt2' | 'llama' | 'keye' | 'kimi' |
    #                              'qwen3_next' | 'lfm2' | 'laguna' |
    #                              'mellum' (laguna's block) |
    #                              'minicpm_sala' | 'nemotron_h' |
    #                              'granite_hybrid' | 'brumby'
    hidden_size: int
    num_hidden_layers: int       # transformer blocks (sublayers = 4x this)
    num_attention_heads: int
    intermediate_size: int
    layer_norm_eps: float = 1e-12
    num_labels: int = 0
    # vision
    image_size: int = 224
    patch_size: int = 16
    num_channels: int = 3
    # text
    vocab_size: int = 0
    max_position_embeddings: int = 0
    type_vocab_size: int = 2
    # mixture-of-experts (switch-FFN blocks; 0 = dense FFN)
    n_experts: int = 0
    capacity_factor: float = 1.25
    # grouped-query attention (llama family): 0 = same as query heads
    num_kv_heads: int = 0
    # rotary position embedding base (llama family)
    rope_theta: float = 10000.0
    # sliding-window attention (Mistral-style): each position attends to
    # the last `sliding_window` positions (incl. itself); 0 = full causal
    sliding_window: int = 0
    # width of one attention head where the model states it apart from
    # hidden_size // heads (0 = that quotient): `head_dim` below
    attn_head_dim: int = 0
    # top-k routed experts without drops (keye family; `n_experts` counts
    # them): the experts' own width, experts a token, and whether the kept
    # gates are renormalised to sum to one
    moe_intermediate_size: int = 0
    num_experts_per_tok: int = 0
    norm_topk_prob: bool = False
    # per-head RMSNorm of q and k before the rotation
    qk_norm: bool = False
    # M-RoPE: how many of a head's rotary frequencies turn with the
    # temporal, height and width position; () = one position row
    mrope_section: tuple = ()
    # learned sparse attention: an indexer of `index_heads` heads of
    # `index_head_dim` scores every live position and the query attends
    # the `index_topk` best (0 = attend all). `index_q_chunk` is the span
    # a prompt is prefilled in.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_q_chunk: int = 0
    # the expert layer's router: 'softmax', or 'sigmoid' (each expert's
    # score on its own, the choice made on score + a learned bias, the
    # kept scores normalised and scaled by `routed_scaling_factor`);
    # `n_shared_experts` experts of the routed width beside the routed ones
    # that every token goes through
    router: str = "softmax"
    routed_scaling_factor: float = 1.0
    # what the kept gates' sum is guarded with where they are renormalised
    # (DeepSeek-V3's code adds 1e-20, LFM2's 1e-6; a softmax's sum needs none)
    gate_sum_eps: float = 0.0
    n_shared_experts: int = 0
    # the chip's share of each expert layer, (first, count) of `n_experts`
    # (`<name>@e<first>+<count>`, models/registry.py); () = all of them
    held_experts: tuple = ()
    # the first `first_k_dense` blocks have a dense FFN of
    # `intermediate_size`, the rest the expert layer (kimi family)
    first_k_dense: int = 0
    # latent attention (MLA, kimi family): the query's and the key/value's
    # low-rank widths, and a head's width without and with rotation, and of
    # its value
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN: (factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale, mscale_all_dim); () = plain rotation. The laguna
    # family's fifth entry is the factor its cosine and sine carry
    # (`attention_factor`), and it has no sixth
    rope_yarn: tuple = ()
    # positions a prompt is prefilled at a time where the family prefills
    # in spans and no other field says (keye's is `index_q_chunk`)
    prefill_chunk: int = 0
    # linear attention beside full attention (qwen3_next family): block i
    # is full attention where (i + 1) % `full_attention_interval` == 0 and
    # a Gated DeltaNet layer elsewhere: `linear_key_heads` key heads of
    # `linear_key_dim` and `linear_value_heads` value heads of
    # `linear_value_dim` (a state of key_dim x value_dim a value head), a
    # depthwise causal convolution `linear_conv_kernel` wide, a span run in
    # chunks of `linear_chunk` positions
    full_attention_interval: int = 0
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_kernel: int = 0
    linear_chunk: int = 0
    # the share of a head's width that the rotation turns (its first lanes)
    partial_rotary_factor: float = 1.0
    # the mixer of each block where a list and no interval says (lfm2
    # family: "conv" | "full_attention"), and how many positions its gated
    # short convolution spans
    layer_types: tuple = ()
    conv_kernel: int = 0
    # laguna family ("full_attention" | "sliding_attention" in
    # `layer_types`): the query heads of each block where the kinds of
    # layer differ in them, and the rotation's base in the window layers
    # (plain rotation of the whole head; `rope_theta`, `rope_yarn` and
    # `partial_rotary_factor` are the full layers')
    layer_heads: tuple = ()
    sliding_rope_theta: float = 0.0
    # ... and whether each head's attention output is multiplied by a gate
    # of its own, `sigmoid(g_proj u)` (laguna has one, mellum none)
    head_gate: bool = False
    # minicpm_sala family ("minicpm4" | "lightning-attn" in `layer_types`):
    # MiniCPM's three scalings (the embedding times `scale_emb`, a block's
    # two deltas times `scale_depth / sqrt(published_layers)`, the head's
    # input over `hidden_size / dim_model_base`; 0 = none), the depth the
    # model was published with, which a cut keeps (`<name>@<blocks>`
    # replaces `num_hidden_layers` alone: the residual's factor and a
    # lightning layer's decay are the whole model's), and the block-sparse
    # attention's sizes: (kernel_size, kernel_stride, block_size, topk,
    # init_blocks, window_size, dense_len), in positions but `topk` and
    # `init_blocks`, in blocks
    scale_emb: float = 0.0
    scale_depth: float = 0.0
    dim_model_base: int = 0
    published_layers: int = 0
    sparse_attention: tuple = ()
    # nemotron_h family ("mamba" | "attention" | "experts" in `layer_types`:
    # a block is ONE sublayer, `h += mixer(norm(h))`). Mamba-2: `ssm_heads`
    # heads of `ssm_head_dim`, a state of `ssm_state` a lane of a head,
    # `ssm_groups` groups of heads that share B and C (`conv_kernel` and
    # `linear_chunk` as above). The expert layer: the routed experts read
    # and write a latent of `moe_latent_size` (0 = the model's width) and
    # the shared expert is `shared_expert_width` wide (0 =
    # `n_shared_experts` times the routed width); an expert without a gate
    # matrix is `down(act(up x))` with `expert_act` ("relu2": the square of
    # a ReLU), one with a gate matrix is a SwiGLU whatever this says
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    moe_latent_size: int = 0
    shared_expert_width: int = 0
    expert_act: str = "silu"
    # granite_hybrid family ("mamba" | "attention" in `layer_types`, each
    # mixer followed by a SwiGLU of `intermediate_size`; the `ssm_*` fields
    # as above): the Granite line's four published constants. The embedding
    # times `scale_emb` (above), each residual branch times
    # `residual_multiplier`, the attention's scores times
    # `attention_multiplier` (in place of `head_dim**-0.5`), the logits over
    # `logits_scaling`; 0 = none
    residual_multiplier: float = 0.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 0.0

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim \
            or self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        """Key/value head count (GQA: fewer than query heads; 0 = equal)."""
        return self.num_kv_heads or self.num_attention_heads

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2


@dataclasses.dataclass(frozen=True)
class QuantizeCompute:
    """Int8 compute-path config (ops/int8_matmul.py).

    `enabled` routes every TAGGED dense (ViT's attention projections,
    attn-out, and the FFN pair — untagged call sites always stay exact)
    through the block-scaled int8 matmul. `skip_tags` is the per-layer
    opt-out for numerically fragile layers (e.g. frozenset({"head"}));
    `clamp_alphas` maps tags to calibrated Banner clip thresholds
    (utils/calibrate.py sidecar); `tunnel` additionally lets a stage's
    first matmul consume the 8-bit wire payload directly
    (parallel/pipeline.py seam, ops/int8_matmul.wire_dense).

    TRACE-TIME config, like fast numerics: programs compiled while a
    config is active keep it — set it BEFORE building/first-calling a
    model.
    """
    enabled: bool = False
    block_k: int = 128
    skip_tags: frozenset = frozenset()
    clamp_alphas: Optional[dict] = None
    tunnel: bool = False


_QC_OFF = QuantizeCompute()
_QUANTIZE_COMPUTE = None   # None = unset (consult the env var)
_QC_OBSERVER = None        # calibration hook: fn(tag, x) per tagged dense


def set_quantize_compute(cfg) -> None:
    """Install the int8 compute-path config.

    `cfg` is a `QuantizeCompute`, True/False (defaults / off), or None to
    RESET: discard the programmatic choice and defer to the env again
    (PIPEEDGE_QUANTIZE_COMPUTE=1 enables the defaults,
    PIPEEDGE_QUANTIZE_SKIP=tag,tag populates the opt-out) — the same
    setter-wins-but-None-restores contract as `set_fast_numerics`.
    """
    global _QUANTIZE_COMPUTE
    if cfg is None or isinstance(cfg, QuantizeCompute):
        _QUANTIZE_COMPUTE = cfg
    else:
        _QUANTIZE_COMPUTE = QuantizeCompute(enabled=bool(cfg))


def quantize_compute() -> QuantizeCompute:
    """The active int8 compute config (programmatic choice wins; env
    PIPEEDGE_QUANTIZE_COMPUTE is the fallback; disabled otherwise)."""
    if _QUANTIZE_COMPUTE is not None:
        return _QUANTIZE_COMPUTE
    import os
    env = os.getenv("PIPEEDGE_QUANTIZE_COMPUTE")
    if env is not None and env.strip().lower() not in (
            "", "0", "false", "no", "off"):
        skip = frozenset(t for t in os.getenv(
            "PIPEEDGE_QUANTIZE_SKIP", "").split(",") if t)
        return QuantizeCompute(enabled=True, skip_tags=skip)
    return _QC_OFF


_FAST_NUMERICS = None      # None = unset (consult the env var)


def set_fast_numerics(enabled) -> None:
    """Opt-in fast-numerics mode (also env PIPEEDGE_FAST_NUMERICS=1 when
    this setter was never called or was reset — the programmatic toggle
    WINS so exact-vs-fast A/Bs can't be silently poisoned by an inherited
    env): LayerNorm statistics and attention softmax run in the model
    dtype instead of float32, and exact-erf GeLU becomes the tanh
    approximation. Trades exact HF/reference numerics parity for fewer
    f32 intermediates (less VPU/HBM traffic between the MXU matmuls) —
    the cost of the parity default is those f32 intermediates, and
    no cell of the benchmark turns this on (it waits for the q8 cells).

    `enabled` is True/False, or None to RESET: discard any programmatic
    choice and defer to PIPEEDGE_FAST_NUMERICS again (without None the
    env opt-in would be permanently dead for the rest of the process
    after any caller touched the toggle).

    TRACE-TIME flag: programs compiled while the mode is on keep it
    (jit caches by shape/dtype, not by this flag) — enable it BEFORE
    building/first-calling a model, and build a fresh jit wrapper for
    each mode of an A/B. Accuracy delta vs the exact mode is held by
    tests/test_models.py (top-1 agreement on the tiny fixtures)."""
    global _FAST_NUMERICS
    _FAST_NUMERICS = None if enabled is None else bool(enabled)


def fast_numerics_enabled() -> bool:
    if _FAST_NUMERICS is not None:
        return _FAST_NUMERICS
    import os
    env = os.getenv("PIPEEDGE_FAST_NUMERICS")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "no", "off")
    return False


def layer_norm(p, x: jax.Array, eps: float) -> jax.Array:
    """LayerNorm with scale/bias, computed in float32 for stability
    (model-dtype statistics under fast-numerics)."""
    if fast_numerics_enabled():
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        normed = (x - mean) * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype))
        return normed * p["scale"].astype(x.dtype) \
            + p["bias"].astype(x.dtype)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (normed * p["scale"] + p["bias"]).astype(x.dtype)


def dense(p, x: jax.Array, tag: Optional[str] = None) -> jax.Array:
    """x @ w + b with kernels stored [in, out] (JAX convention; torch state
    dicts store [out, in] and are transposed at load time).

    `tag` names the call site for the int8 compute path: tagged denses
    route through the block-scaled int8 matmul when a `QuantizeCompute`
    config is active (and the tag isn't opted out); untagged denses are
    always exact. The calibration observer hook also keys on tags."""
    if tag is not None:
        if _QC_OBSERVER is not None:
            _QC_OBSERVER(tag, x)
        qc = quantize_compute()
        if qc.enabled and tag not in qc.skip_tags:
            from ..ops import int8_matmul
            alpha = (qc.clamp_alphas or {}).get(tag)
            return int8_matmul.int8_dense(
                x, p["w"], p["b"], block_k=qc.block_k, clamp_alpha=alpha,
                out_dtype=x.dtype)
    y = jnp.dot(x, p["w"].astype(x.dtype), preferred_element_type=jnp.float32)
    return (y + p["b"]).astype(x.dtype)


def exact_dot(x: jax.Array, w: jax.Array, w_contract: int = 0) -> jax.Array:
    """x [..., K] times w over w's axis `w_contract` -> float32 [..., N].

    Float32 activations over narrower weights (bfloat16 as stored): x is
    split into three bfloat16 parts that add up to it to 24 bits, the parts
    are stacked as rows of ONE product with w as it lies (read once, never
    widened), and the three results are added in float32. Every product of
    two bfloat16 values is exact in float32, so this is the accuracy of
    `Precision.HIGHEST` at three passes instead of six. Both float32: one
    product at HIGHEST. Otherwise (both narrow): the plain product,
    accumulated in float32."""
    def dot(a, precision=None):
        return jax.lax.dot_general(
            a, w, (((a.ndim - 1,), (w_contract,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    if x.dtype != jnp.float32:
        return dot(x.astype(w.dtype))
    if w.dtype == jnp.float32:
        return dot(x, jax.lax.Precision.HIGHEST)
    whole = dot(_three_parts(x, w.dtype))     # [3, ..., N]
    return whole[0] + whole[1] + whole[2]


def _three_parts(x: jax.Array, dtype) -> jax.Array:
    """Float32 x as three `dtype` (bfloat16) parts, stacked, that add up to
    it to 24 bits. `reduce_precision` and not a cast there and back: inside
    a compiled program the chip's compiler may keep the excess precision of
    such a pair, the remainder is then zero and the product a single
    bfloat16 pass (1.7e-3 off where this is 1e-7; my chip runs, PR 27)."""
    parts, rest = [], x
    for _ in range(3):
        part = jax.lax.reduce_precision(rest, exponent_bits=8,
                                        mantissa_bits=7)
        parts.append(part.astype(dtype))
        rest = rest - part
    return jnp.stack(parts)


def exact_einsum(spec: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """`jnp.einsum(spec, x, w)` in float32 with `exact_dot`'s rule for
    activations x over weights w, for products `exact_dot` cannot spell (a
    head axis that both carry). `spec` names no axis `z`.

    The three parts stay float32 arrays here (of values a bfloat16 holds)
    and w is widened beside them: the one pass the chip makes of a float32
    product at `DEFAULT` rounds both to bfloat16, which changes neither, and
    the CPU has no bfloat16 product with a batch axis at all."""
    one_pass = jax.lax.Precision.DEFAULT
    if x.dtype != jnp.float32:
        return jnp.einsum(spec, x.astype(jnp.float32),
                          w.astype(jnp.float32), precision=one_pass)
    if w.dtype == jnp.float32:
        return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)
    lhs, rest = spec.split(",")
    whole = jnp.einsum(f"z{lhs},{rest.replace('->', '->z')}",
                       _three_parts(x, w.dtype).astype(jnp.float32),
                       w.astype(jnp.float32), precision=one_pass)
    return whole[0] + whole[1] + whole[2]


def _use_fused_attention(seq_len: int) -> bool:
    """The streaming Pallas kernel (`ops/attention.py::fused_attention`): on
    a TPU from 1,024 positions, where streaming the [S, S] scores through
    VMEM beats XLA (measured ~5x at S=8192). Shorter rows are the short
    core's where `_short_core_mode` says so (ViT's 197, DeiT's 198) and the
    einsums' otherwise (BERT's and GPT-2's 512). Override with env
    PIPEEDGE_FUSED_ATTENTION=0/1, which decides between this kernel and the
    einsums and never reaches the short core."""
    import os
    env = os.getenv("PIPEEDGE_FUSED_ATTENTION")
    if env is not None:
        return env not in ("0", "false", "no")
    return jax.default_backend() == "tpu" and seq_len >= 1024


# /metrics plane: which form an uncached block's attention core took, counted
# where `self_attention` chooses, so once a block a program TRACED (a program
# that scans its blocks counts one, an unrolled stage program one a block)
_M_CORE_BLOCKS = prom.REGISTRY.counter(
    "pipeedge_attn_core_blocks_total",
    "attention cores `layers.self_attention` traced, by the form it chose "
    "from the call: fused = a kernel of ops/ (the short core, or the "
    "streaming one from 1,024 positions), einsum = XLA's two einsums; a "
    "`core_fn` override is not counted")
for _path in ("fused", "einsum"):
    _M_CORE_BLOCKS.declare(path=_path)


def _kernel_mode():
    """How this backend runs the short attention core
    (`ops/short_attention.py`): "mosaic" on a TPU, None where Mosaic cannot
    run (the einsums serve every call); the tests put "interpret" here."""
    return "mosaic" if jax.default_backend() == "tpu" else None


def _short_core_mode(seq_len: int, width: int, num_heads: int, dtype):
    """How an unmasked, non-causal core over q, k, v `[B, seq_len, width]`
    of `num_heads` heads runs, read off the call: `_kernel_mode()` where the
    short kernel takes it (heads of 64 or 128 in whole slabs of lanes, its
    blocks and scratch within its VMEM budget: `ops/short_attention.py::
    takes`, a function of S, H * Dh and the type; exact numerics, since the
    kernel's softmax is the float32 one), None where the einsums keep it:
    the CPU, ViT-H's heads of 80, rows past 256 at ViT-L's width. At ViT-L's
    call (8 x 197 x 16 heads of 64, bfloat16) the kernel is 27.0 us a block
    in the four-chip cell's traced window where the einsums with their three
    transposed copies are 35.2 (PERF.md section 6, PR 60); no cell measures
    the rule at another length. The kernel's module is imported only on a
    backend that could run it, for a call without mask or `causal`."""
    mode = _kernel_mode()
    if mode is None or fast_numerics_enabled():
        return None
    from ..ops import short_attention
    fits = short_attention.takes(seq_len, width, width // num_heads,
                                 jnp.dtype(dtype).itemsize)
    return mode if fits else None


def rms_norm(p, x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm (scale only, no mean subtraction — llama family), computed
    in float32 like HF `LlamaRMSNorm`."""
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1,
                                         keepdims=True) + eps)
    return (normed * p["scale"]).astype(x.dtype)


def rope_frequencies(head_dim: int, theta: float):
    """The rotation's head_dim/2 frequencies, float32, computed on the host
    (HF's formula): a constant of the program. The chip's own `pow` is a
    few 1e-6 off, which at position 16,000 is hundredths of a radian."""
    import numpy as np
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def yarn_frequencies(dim: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """The `dim / 2` frequencies of a rotation over `dim` lanes under YaRN
    (the `transformers` library's computation, on the host): those that
    turn more than `beta_fast` times in the `original` context stay, those
    that turn fewer than `beta_slow` times are divided by `factor`, a
    linear ramp between."""
    import math

    import numpy as np
    freqs = rope_frequencies(dim, theta)

    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    return (freqs / factor * ramp + freqs * (1 - ramp)).astype(np.float32)


def rotate_halves(x: jax.Array, pos: jax.Array, freqs,
                  scale: float = 1.0) -> jax.Array:
    """x [B, S, H, Dh] turned at positions `pos` [S] by the Dh/2 `freqs`
    (HF llama convention: half-split rotate, angles in float32, one
    frequency per pair duplicated across the two halves); cosine and sine
    times `scale` where a scheme (YaRN's attention factor) carries one."""
    angles = pos.astype(jnp.float32)[:, None] * freqs[None]      # [S, hd/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)        # [S, hd]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos[None, :, None] + rotated
            * sin[None, :, None]).astype(x.dtype)


def rope_rotate(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding on [B, S, H, Dh] at positions `pos` [S]:
    `rotate_halves` by the plain frequencies of base `theta`."""
    return rotate_halves(x, pos, rope_frequencies(x.shape[-1], theta))


def causal_conv(kernel: jax.Array, x: jax.Array, tail: jax.Array):
    """Depthwise causal convolution of x [B, S, C] with `kernel` [K, C],
    carried across calls: position t sees x at t - K + 1 .. t, the K - 1
    positions before the span coming from `tail` [B, K - 1, C] (zeros at a
    prompt's start). -> (mixed [B, S, C] float32, the tail after the span:
    the last K - 1 inputs, whatever S)."""
    s = x.shape[1]
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    kernel = kernel.astype(jnp.float32)
    mixed = sum(kernel[j] * padded[:, j:j + s]
                for j in range(kernel.shape[0]))
    return mixed, padded[:, s:]


def apply_causal_mask(scores: jax.Array) -> jax.Array:
    """Mask strictly-future key positions in [..., S_q, S_k] scores
    (shared by the XLA attention path and the TP block bodies)."""
    s_q, s_k = scores.shape[-2], scores.shape[-1]
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
    return jnp.where(k_pos <= q_pos, scores, -1e30)


def einsum_core(q: jax.Array, k: jax.Array, v: jax.Array,
                causal: bool = False,
                mask: Optional[jax.Array] = None) -> jax.Array:
    """softmax(q k^T / sqrt(Dh)) v as XLA's two einsums over q, k, v
    `[B, S, H, Dh]` -> the context `[B, S, H, Dh]`: `self_attention`'s core
    wherever no kernel takes it, and, unmasked, what the short kernel's
    backward differentiates (`ops/short_attention.py`), so the two cannot
    drift apart. Scores and softmax in float32, the probabilities cast to
    the operands' type before the second product."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    if causal:
        scores = apply_causal_mask(scores)
    if mask is not None:
        # mask: [B, S] with 1 = attend, 0 = ignore
        bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e9).astype(jnp.float32)
        scores = scores + bias
    if fast_numerics_enabled():
        # model-dtype softmax: the MXU accumulation above stays f32
        # (free); only the VPU softmax intermediates narrow
        probs = jax.nn.softmax(scores.astype(q.dtype), axis=-1)
    else:
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def self_attention(p, x: jax.Array, num_heads: int,
                   mask: Optional[jax.Array] = None,
                   core_fn=None, causal: bool = False,
                   tag_prefix: Optional[str] = None) -> jax.Array:
    """Multi-head self-attention context (pre-projection), batched over [B,S,D].

    Matches HF `{ViT,Bert}SelfAttention` semantics: returns the concatenated
    per-head context; the output projection lives in the next sublayer
    (reference vit.py:58-63). Softmax in float32.

    The softmax(QK^T)V core takes one of three forms, read off the call (no
    option chooses; `pipeedge_attn_core_blocks_total{path}` counts each
    traced core): unmasked and non-causal on a TPU, with heads of 64 or 128
    and a row short enough for VMEM, the short kernel
    (`ops/short_attention.py`, q, k, v read as `dense` wrote them:
    `_short_core_mode`); unmasked from 1,024 positions on a TPU the
    streaming kernel (`ops/attention.py`: `_use_fused_attention`); XLA's
    einsums everywhere else (the CPU, a padding mask, a short causal row).
    The short kernel and the einsums can be differentiated (the kernel's
    backward is the einsums'); the streaming kernel cannot.

    `causal` applies a lower-triangular mask (decoder families, e.g. GPT-2);
    the streaming kernel handles it natively (and skips past-frontier K/V
    blocks), so the long-sequence perf path covers decoders too.

    `tag_prefix` tags the q/k/v projections (`<prefix>.q` etc.) for the
    int8 compute path — see `dense`.

    `core_fn(q, k, v) -> ctx` ([B,S,H,D]-shaped) overrides the attention
    core while reusing THIS projection code — how sequence-parallel
    execution swaps in ring attention (parallel/spmd.py). A core_fn is
    responsible for its own causal masking (ring/Ulysses attention take a
    `causal` flag), so `causal` is ignored on that path.
    """
    b, s, d = x.shape
    hd = d // num_heads
    tags = {n: f"{tag_prefix}.{n}" if tag_prefix else None
            for n in ("q", "k", "v")}
    q = dense(p["q"], x, tag=tags["q"]).reshape(b, s, num_heads, hd)
    k = dense(p["k"], x, tag=tags["k"]).reshape(b, s, num_heads, hd)
    v = dense(p["v"], x, tag=tags["v"]).reshape(b, s, num_heads, hd)
    if core_fn is not None:
        if mask is not None:
            # the override receives no mask; reject the combination rather
            # than silently attending to padding tokens
            raise NotImplementedError(
                "core_fn overrides do not support masks")
        return core_fn(q, k, v).reshape(b, s, d)
    if mask is None and _use_fused_attention(s):
        from ..ops.attention import fused_attention
        _M_CORE_BLOCKS.inc(path="fused")
        return fused_attention(q, k, v, causal=causal).reshape(b, s, d)
    mode = None if causal or mask is not None \
        else _short_core_mode(s, d, num_heads, q.dtype)
    if mode is not None:
        # the projections' own lay-out: the reshapes above are views
        from ..ops.short_attention import short_attention
        _M_CORE_BLOCKS.inc(path="fused")
        return short_attention(q.reshape(b, s, d), k.reshape(b, s, d),
                               v.reshape(b, s, d), num_heads, einsum_core,
                               mode == "interpret")
    _M_CORE_BLOCKS.inc(path="einsum")
    return einsum_core(q, k, v, causal, mask).reshape(b, s, d)


def gelu(x: jax.Array) -> jax.Array:
    """Exact (erf) GeLU, matching torch `nn.GELU()` default used by HF
    (tanh approximation under fast-numerics): `_erf_gelu`, in float32 from
    the input whatever its floating type."""
    if fast_numerics_enabled():
        return jax.nn.gelu(x, approximate=True)
    return _erf_gelu(x)


# `tools/fit_gelu.py` prints these: Phi(-a) exp(a^2 / 2) = t * P(t) for
# t = 1 / (a + GELU_C), to 4.3e-8 relative up to a = 6.6 (past it a float32
# GeLU is under 2^-30) and 5.3e-5 up to GELU_CLAMP, where the `exp` is 0
GELU_C = 3.5
GELU_K = (0.39774173498153687, 1.457862138748169, 3.134087562561035,
          29.51571273803711, -90.99691009521484, 605.0781860351562,
          -1265.1190185546875, 862.3145141601562)
GELU_CLAMP = 14.0
INV_SQRT_2PI = 0.3989422804014327


def _leading_bits(v: jax.Array, bits: int) -> jax.Array:
    """float32 `v` with all but its first `bits` significant bits cleared:
    the part of a compensated sum or square that is exact by construction.
    By a mask, because XLA folds the arithmetic spellings away: it rewrites
    `a - ((a + c) - c)` to 0 and `exp(p) * exp(q)` to `exp(p + q)`."""
    kept = jax.lax.bitcast_convert_type(v, jnp.uint32) \
        & jnp.uint32(0xFFFFFFFF << (24 - bits) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(kept, jnp.float32)


def _gelu_parts(x: jax.Array):
    """(x, a, t P(t), exp(-a^2 / 2)) in float32, `a = min(|x|, GELU_CLAMP)`:
    what the value and the slope of `_erf_gelu` are both made of.

    A bfloat16 input's eight bits leave `a * a` exact in float32, and the
    rounding of `a + c` is sixteen bits under the output's. An input of more
    bits (float32: BERT, the tensor-parallel block and the expert layer run
    it) rounds the square by half a float32 ulp, which the `exp` multiplies
    by a^2 / 2 (4.5 at a = 3), and the sum by another half, which `t P(t)`
    multiplies by up to 2.8: 7.7 ulp with the dozen other roundings. So
    there both are taken of leading bits, which ARE exact, and what the bits
    dropped is put back to first order: `exp(-a^2 / 2) = exp(-h^2 / 2)
    (1 + r + r^2 / 2)`, `r = -(a - h)(a + h) / 2`, and `G(a) = G(a') +
    (a - a') (a G(a') - 1 / sqrt(2 pi))` for `G = t P(t)`: 4.9 ulp, and 3.4
    on ISSUE 61's grid (`tests/test_gelu_exact.py`). Seventeen more
    operations, none of them in a bfloat16 program."""
    xf = x.astype(jnp.float32)
    a = jnp.minimum(jnp.abs(xf), GELU_CLAMP)
    wide = x.dtype != jnp.bfloat16
    shifted = _leading_bits(a + GELU_C, 16) if wide else a + GELU_C
    t = 1.0 / shifted
    poly = jnp.float32(GELU_K[-1])
    for k in GELU_K[-2::-1]:
        poly = poly * t + k
    tail = poly * t
    root = _leading_bits(a, 12) if wide else a
    decay = jnp.exp(root * root * -0.5)
    if wide:
        tail = tail + (a - (shifted - GELU_C)) * (a * tail - INV_SQRT_2PI)
        r = (a - root) * (a + root) * -0.5
        decay = decay + decay * (r + r * r * 0.5)
    return xf, a, tail, decay


def _gelu_value(dtype, xf, a, tail, decay) -> jax.Array:
    # a * t * P first: near 0.4 in the tail, so the product with the decay
    # is subnormal only where the GeLU is
    return (jnp.maximum(xf, 0.0) - (a * tail) * decay).astype(dtype)


@jax.custom_jvp
def _erf_gelu(x: jax.Array) -> jax.Array:
    """`x Phi(x)` as `max(x, 0) - a Phi(-a)`, `a = |x|`, in float32: one
    branch for every x, one divide and one `exp`, and the tail keeps its
    RELATIVE accuracy because `Phi(-a) = t P(t) exp(-a^2 / 2)` leaves the
    decay to the `exp` (docstring of `tools/fit_gelu.py`).

    `jax.nn.gelu`'s `0.5 x erfc(-x / sqrt 2)` is, on a TPU, XLA's float32
    `erfc`: both of its ranges' polynomials, an `exp` and two divides,
    computed and selected, some 90 vector operations a value for the 40
    here, which trail the ViT block's up product (PERF.md section 6, PR 61);
    and on a bfloat16 input it rounds `sqrt 1/2` and the products to
    bfloat16, where this form is the correctly rounded GeLU at every
    bfloat16 input whose value is above 2^-24 (`tests/test_gelu_exact.py`).

    `a` is clamped so that an infinite x meets 0 and not `inf * 0`: -inf
    gives 0 (`jax.nn.gelu` gives NaN there). The slope is written out,
    `Phi(x) + x phi(x)` from the parts the value is made of: differentiating
    through the polynomial, the divide and the `exp` would give the same
    number for twice the backward's work."""
    return _gelu_value(x.dtype, *_gelu_parts(x))


@_erf_gelu.defjvp
def _erf_gelu_jvp(primals, tangents):
    (x,), (dx,) = primals, tangents
    xf, a, tail, decay = _gelu_parts(x)
    below = tail * decay                                    # Phi(-a)
    slope = jnp.where(xf < 0, below, 1.0 - below) + xf * decay * INV_SQRT_2PI
    return (_gelu_value(x.dtype, xf, a, tail, decay),
            (dx.astype(jnp.float32) * slope).astype(x.dtype))


def gelu_new(x: jax.Array) -> jax.Array:
    """Tanh-approximate GeLU, matching HF `gelu_new` (GPT-2's activation)."""
    return jax.nn.gelu(x, approximate=True)


def patchify(x: jax.Array, patch: int) -> jax.Array:
    """[B, H, W, C] -> [B, N, patch*patch*C] with (ph, pw, c) flattening order.

    Expressing patch embedding as reshape + one big matmul (instead of a
    strided conv) maps directly onto the MXU; the kernel layout matches, e.g.,
    Google's ViT npz `embedding/kernel` [ph, pw, C, D] reshaped to
    [ph*pw*C, D] (reference vit.py:124-128 does the conv-layout dance instead).
    """
    b, h, w, c = x.shape
    nh, nw = h // patch, w // patch
    x = x.reshape(b, nh, patch, nw, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, nh * nw, patch * patch * c)
