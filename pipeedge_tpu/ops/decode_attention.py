"""Fused int8-KV decode-step attention as a Pallas TPU kernel.

The XLA int8 decode path (models/stage_cache.py `cache_update_and_read`)
dequantizes the attended cache window to a full-precision [B, T, H, Dh]
copy before the attend matmuls — XLA does not fuse elementwise producers
into dot operands, so the dequantized K AND V copies are materialized
through HBM every decode step. This kernel streams the int8 cache
blocks into VMEM, dequantizes in-register, and runs the online-softmax
attend — HBM reads stay int8 (plus the tiny per-(position, head) scale
rows), roughly halving the decode step's dominant traffic.

Semantics match the XLA path exactly where it matters:
- the FRESH row (the token written at `pos` this step) is substituted
  unquantized inside the kernel, mirroring the XLA path's
  "freshly computed rows are in hand — attend over them exactly";
- masking keeps cache positions [0, pos]; K/V blocks wholly past `pos`
  are skipped (the streaming loop stops at the last live block, which
  is also what the bucketed attend window achieves statically).

Scope: the classic single-token decode step of MHA families
(kv_heads == num heads, no sliding window) — the hot serving path.
Span (speculative verify), GQA, and windowed attention stay on the XLA
path. `pos` reaches the kernel via scalar prefetch (it is traced; the
window width is static).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._blocks import pick_block

_NEG_INF = -1e30


def _kernel(pos_ref, q_ref, kq_ref, ks_ref, kz_ref, vq_ref, vs_ref, vz_ref,
            kn_ref, vn_ref, o_ref, *, kv_block: int, scale: float):
    """One batch cell, ALL heads at once: stream int8 K/V row-blocks,
    dequantize in VMEM, online softmax per head over positions [0, pos].

    The head axis stays in the block (TPU lowering requires the last two
    block dims be full or tile-aligned, so a per-head grid would need a
    layout transpose — materializing the copy this kernel exists to
    avoid). At S_q=1 the attend is bandwidth-bound elementwise+reduce
    work; everything maps to the VPU, no MXU involvement."""
    pos = pos_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)                  # [H, Dh]
    width, h, d = kq_ref.shape[1], q.shape[0], q.shape[1]
    n_kv = width // kv_block

    k_new = kn_ref[0, 0].astype(jnp.float32)             # [H, Dh]
    v_new = vn_ref[0, 0].astype(jnp.float32)

    def dequant(qv, s_ref, z_ref, i):
        s = s_ref[0, pl.ds(i * kv_block, kv_block), :]   # [kb, H]
        z = z_ref[0, pl.ds(i * kv_block, kv_block), :]
        return (qv.astype(jnp.float32) + 128.0) * s[..., None] + z[..., None]

    def body(i, carry):
        m_prev, l_prev, acc = carry                      # [H] [H] [H, Dh]
        rows = i * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (kv_block, h), 0)                 # [kb, H]
        k = dequant(kq_ref[0, pl.ds(i * kv_block, kv_block)],
                    ks_ref, kz_ref, i)                   # [kb, H, Dh]
        v = dequant(vq_ref[0, pl.ds(i * kv_block, kv_block)],
                    vs_ref, vz_ref, i)
        # 3D iota, not rows[..., None]: Mosaic only supports minor-dim
        # insertion for 32-bit types, and the mask is boolean
        fresh = (i * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (kv_block, h, 1), 0)) == pos      # [kb, H, 1]
        k = jnp.where(fresh, k_new[None], k)
        v = jnp.where(fresh, v_new[None], v)
        # round K/V (and below, the probs) through the pipeline dtype at
        # the same points the XLA path does (dequantize_rows -> dtype,
        # probs.astype(dtype)); f32 pipelines make these no-ops. The
        # online softmax still differs from the full softmax at the
        # rounding level — flash-style accumulation is mathematically,
        # not bitwise, equal.
        k = k.astype(o_ref.dtype).astype(jnp.float32)
        v = v.astype(o_ref.dtype).astype(jnp.float32)
        scores = jnp.sum(q[None] * k, axis=-1) * scale   # [kb, H]
        scores = jnp.where(rows <= pos, scores, _NEG_INF)
        m_blk = jnp.max(scores, axis=0)                  # [H]
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(scores - m_new[None])                # [kb, H]
        p = p.astype(o_ref.dtype).astype(jnp.float32)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=0)
        acc = acc * corr[:, None] + jnp.sum(p[..., None] * v, axis=0)
        return m_new, l_new, acc

    m0 = jnp.full((h,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((h,), jnp.float32)
    acc0 = jnp.zeros((h, d), jnp.float32)
    n_live = jnp.minimum(pos // kv_block + 1, n_kv)   # skip dead blocks
    _, l, acc = jax.lax.fori_loop(0, n_live, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)


def _kernel_v2(pos_ref, q_ref, kq_ref, ks_ref, kz_ref, vq_ref, vs_ref,
               vz_ref, kn_ref, vn_ref, o_ref, m_scr, l_scr, acc_scr, *,
               kv_block: int, scale: float, n_kv: int):
    """v2 'batch-as-sublane' formulation (round-5 verdict item 3): the
    grid runs over KV row-blocks (sequential, online-softmax state in
    VMEM scratch) and each instance processes EVERY batch cell at once —
    [B, kb, H, Dh] element blocks give the VPU B x more rows per
    instruction than v1's per-cell grid, and the kernel launches n_kv
    instances instead of B. Same masking/fresh-row/rounding semantics
    as v1 (the exactness tests parametrize over both)."""
    i = pl.program_id(0)
    pos = pos_ref[0]
    q = q_ref[:, 0].astype(jnp.float32)                  # [B, H, Dh]
    b, h, d = q.shape

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full((b, h), _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros((b, h), jnp.float32)
        acc_scr[...] = jnp.zeros((b, h, d), jnp.float32)

    def dequant(qv, s, z):
        return (qv.astype(jnp.float32) + 128.0) * s[..., None] \
            + z[..., None]

    k_new = kn_ref[:, 0].astype(jnp.float32)             # [B, H, Dh]
    v_new = vn_ref[:, 0].astype(jnp.float32)
    k = dequant(kq_ref[...], ks_ref[...], kz_ref[...])   # [B, kb, H, Dh]
    v = dequant(vq_ref[...], vs_ref[...], vz_ref[...])
    rows4 = i * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (b, kv_block, h, 1), 1)
    fresh = rows4 == pos
    k = jnp.where(fresh, k_new[:, None], k)
    v = jnp.where(fresh, v_new[:, None], v)
    k = k.astype(o_ref.dtype).astype(jnp.float32)
    v = v.astype(o_ref.dtype).astype(jnp.float32)
    scores = jnp.sum(q[:, None] * k, axis=-1) * scale    # [B, kb, H]
    rows3 = i * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (b, kv_block, h), 1)
    scores = jnp.where(rows3 <= pos, scores, _NEG_INF)
    m_prev, l_prev, acc = m_scr[...], l_scr[...], acc_scr[...]
    m_blk = jnp.max(scores, axis=1)                      # [B, H]
    m_new = jnp.maximum(m_prev, m_blk)
    p = jnp.exp(scores - m_new[:, None])                 # [B, kb, H]
    p = p.astype(o_ref.dtype).astype(jnp.float32)
    corr = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc * corr[..., None] + jnp.sum(p[..., None] * v,
                                                   axis=1)

    @pl.when(i == n_kv - 1)
    def _emit():
        o_ref[:, 0] = (acc_scr[...]
                       / l_scr[...][..., None]).astype(o_ref.dtype)


# one block resolver across the fused kernels (ops/_blocks.py)
_pick_block = pick_block


_V2_VMEM_BUDGET = 8 << 20


def _pick_block_v2(width: int, b: int, h: int, d: int) -> int:
    """v2 stages [B, kb, H, Dh] blocks with ~6 f32-sized intermediates
    (dequantized K/V, probs, masks) live at once — cap kb so the scoped
    VMEM stack stays well under the ~16 MB limit (measured OOM at
    B=16, kb=128: 24.3 MB requested). Returns 0 when even the minimum
    kb=8 block busts the budget (huge B*H*Dh): callers refuse variant 2
    for that shape instead of dying in Mosaic lowering."""
    per_row = b * h * d * 4 * 6
    if per_row * 8 > _V2_VMEM_BUDGET:
        return 0
    preferred = min(128, _V2_VMEM_BUDGET // per_row) // 8 * 8
    block = _pick_block(width, preferred)
    # _pick_block falls back to the FULL width when no divisor >= 8
    # exists (e.g. width 100); re-check the budget on what it actually
    # returned rather than trusting the preference
    return block if block * per_row <= _V2_VMEM_BUDGET else 0


def int8_v2_fits(width: int, b: int, h: int, d: int) -> bool:
    """Whether the batch-as-sublane variant has a legal block size for
    this shape (decode.py's routing gate falls back to the XLA path
    when not)."""
    return _pick_block_v2(width, b, h, d) > 0


@functools.partial(jax.jit, static_argnames=("interpret", "variant"))
def int8_decode_attention(q, k_q, k_scale, k_shift, v_q, v_scale, v_shift,
                          k_new, v_new, pos, interpret: bool = False,
                          variant: int = 1):
    """Fused decode-step attention over an int8 cache window.

    q/k_new/v_new: [B, 1, H, Dh]; k_q/v_q: [B, T, H, Dh] int8;
    scales/shifts: [B, T, H] float32; `pos` traced scalar. Returns
    [B, 1, H*Dh] context, matching `attend`'s output layout.

    `variant` 1: per-batch-cell grid, fori_loop over KV blocks (live
    blocks only). `variant` 2: per-KV-block grid processing all batch
    cells at once ('batch-as-sublane'), online-softmax state in VMEM
    scratch — B x the VPU rows per instruction, n_kv instead of B
    kernel instances, at the cost of always touching the full (bucketed)
    window. Numerically identical routes (shared exactness tests)."""
    b, _, h, d = q.shape
    width = k_q.shape[1]
    kv_block = _pick_block_v2(width, b, h, d) if variant == 2 \
        else _pick_block(width)
    scale = 1.0 / (d ** 0.5)
    if variant == 2:
        if kv_block == 0:
            raise ValueError(
                f"int8 decode kernel variant 2 has no legal block for "
                f"B={b}, H={h}, Dh={d} within the VMEM budget; use "
                "variant 1 or the XLA path (int8_v2_fits gates this)")
        n_kv = width // kv_block
        kernel = functools.partial(_kernel_v2, kv_block=kv_block,
                                   scale=scale, n_kv=n_kv)
        whole = lambda i, *_: (0, 0, 0, 0)
        whole3 = lambda i, *_: (0, 0, 0)
        blk = lambda i, *_: (0, i, 0, 0)
        blk3 = lambda i, *_: (0, i, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_kv,),
            in_specs=[
                pl.BlockSpec((b, 1, h, d), whole),        # q
                pl.BlockSpec((b, kv_block, h, d), blk),   # k_q
                pl.BlockSpec((b, kv_block, h), blk3),     # k_scale
                pl.BlockSpec((b, kv_block, h), blk3),     # k_shift
                pl.BlockSpec((b, kv_block, h, d), blk),   # v_q
                pl.BlockSpec((b, kv_block, h), blk3),     # v_scale
                pl.BlockSpec((b, kv_block, h), blk3),     # v_shift
                pl.BlockSpec((b, 1, h, d), whole),        # k_new
                pl.BlockSpec((b, 1, h, d), whole),        # v_new
            ],
            out_specs=pl.BlockSpec((b, 1, h, d), whole),
            scratch_shapes=[
                pltpu.VMEM((b, h), jnp.float32),          # running max
                pltpu.VMEM((b, h), jnp.float32),          # running sum
                pltpu.VMEM((b, h, d), jnp.float32),       # running acc
            ],
        )
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    else:
        kernel = functools.partial(_kernel, kv_block=kv_block, scale=scale)
        batch_row = lambda b_, *_: (b_, 0, 0, 0)
        batch_row3 = lambda b_, *_: (b_, 0, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, 1, h, d), batch_row),        # q
                pl.BlockSpec((1, width, h, d), batch_row),    # k_q
                pl.BlockSpec((1, width, h), batch_row3),      # k_scale
                pl.BlockSpec((1, width, h), batch_row3),      # k_shift
                pl.BlockSpec((1, width, h, d), batch_row),    # v_q
                pl.BlockSpec((1, width, h), batch_row3),      # v_scale
                pl.BlockSpec((1, width, h), batch_row3),      # v_shift
                pl.BlockSpec((1, 1, h, d), batch_row),        # k_new
                pl.BlockSpec((1, 1, h, d), batch_row),        # v_new
            ],
            out_specs=pl.BlockSpec((1, 1, h, d), batch_row),
        )
        compiler_params = None
    kwargs = {}
    if compiler_params is not None:
        kwargs["compiler_params"] = compiler_params
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, 1, h, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        **kwargs,
    )(jnp.asarray(pos, jnp.int32).reshape(1), q, k_q,
      k_scale.astype(jnp.float32), k_shift.astype(jnp.float32), v_q,
      v_scale.astype(jnp.float32), v_shift.astype(jnp.float32),
      k_new, v_new)
    return out.reshape(b, 1, h * d)


def int8_decode_attention_supported() -> bool:
    """Native lowering needs a TPU; elsewhere interpret mode (tests)."""
    return jax.default_backend() == "tpu"
