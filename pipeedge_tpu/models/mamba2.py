"""The Mamba-2 mixer, and the attention without positions that the hybrids
put between such layers: what nemotron_h and granite_hybrid share. Either
family's module holds its trunk (one sublayer a block, or a mixer then a
SwiGLU), its kinds of block and its key map, and imports this.

**Mamba-2** (`mamba`), `H` = `cfg.ssm_heads` heads of `P` = `cfg.ssm_head_dim`,
a state of `N` = `cfg.ssm_state` a lane, `G` = `cfg.ssm_groups` groups of `H /
G` heads that share B and C (heads `(H / G) g .. (H / G)(g + 1) - 1` on group
`g`; with `G` = 1 every head reads the one B and C and the gated norm is a
plain RMSNorm over all `H P` lanes):
  [z | xBC | dt] = in_proj(u)                   H P + (H P + 2 G N) + H
  xBC = silu(conv(xBC) + b)    depthwise, causal, `cfg.conv_kernel` wide
  [x | B | C] = xBC                             H P + G N + G N
  dt_t = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) dt_t)   a head
  S_t = a_t S_(t-1) + dt_t x_t B_t^T            [P, N] a head
  y_t = S_t C_t + D x_t
  out_proj(GroupRMSNorm(y * silu(z)))  the gate BEFORE the norm, whose
                                       groups are the G groups' H P / G lanes
(`ssm_step`, what a decode step runs). A span runs in chunks of `C` =
`cfg.linear_chunk` (`ssm_chunked`): with `l_i` the sum of `log a` up to `i`
inside the chunk,
  Y = ((C B^T) . L) (dt x) + exp(l) C S_prev + D x,   L_ij = exp(l_i - l_j)
  S_new = exp(l_C) S_prev + sum_i exp(l_C - l_i) dt_i x_i B_i^T
with no inverse. `l` is a triangular product at `HIGHEST` (the chip's
`cumsum` is one bfloat16 pass: PERF.md row 29); every decay is the `exp` of a
difference of sums, never a running product, and the two that compound (a
step's `a_t`, a chunk's `exp(l_C)`) are `decoder.exp_ulp`'s. A last chunk
that the span does not fill is padded with `dt` = 0, which leaves the state.

**Attention** (`attention`). GQA, no bias, no q/k norm and NO ROTATION
(position comes from the Mamba layers); causal softmax at the family's
`scale` (`Dh**-0.5` where it names none: the products have that built in,
so the queries carry the rest). A step reads the window in its stored form
(models/stage_cache.py `attend`); a span's masked softmax is
`decoder.attend_masked`, a KV group at a time.

**Cache** (`cache_leaves`): the Mamba-2 blocks own `ssm_state` `[L_mamba, B,
H, P, N]` (4.19 MB a request a layer at nemotron_h's published sizes, 2.10
MB at granite_hybrid's) and `ssm_conv` `[L_mamba, B, K - 1, H P + 2 G N]`, a
row a REQUEST, read and replaced whole by every call. The decode driver
gathers no second copy of such a leaf where it is large
(`parallel/decode.py::WHOLE_IN_PLACE_BYTES`, a choice a leaf). A span's
state goes into the stack as its block leaves it, fenced to the run. A step's
is moved on where it lies: on a backend that runs Mosaic `ops/ssm_step.py`
reads a tile of the layer's state, updates it, reduces it against C and
writes it back to the block it came from, the stack aliased in and out
(`state_kernel_mode`, read off the call: a step, a float32 leaf the driver
places, a head's state of whole tiles), where the jnp step behind the
driver's update moves a layer three times (XLA computes `a S + dt x B^T`
once for `y` and once more for the update). Elsewhere the jnp step serves
(`ssm_step`; the tests put "interpret" into `_kernel_mode`).

**Precision.** Weights as stored (bfloat16; `A_log`, `D`, `dt_bias`
float32); activations, state, tail, keys and values float32: products with
weights through `exact_dot`, `C B^T`, the state's products and q.k at
`HIGHEST`, a step's state update float32 multiplications and sums on the
vector unit, in the kernel as in the jnp step (three products and a sum an
element, the decay `exp_ulp`'s, the sum over N in the lanes' order; nothing
of it through the matrix unit or a bfloat16 pass).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import ssm_step as ssm_kernel
from . import decoder
from .decoder import by_head, exp_ulp, lin
from .layers import TransformerConfig, causal_conv
from .shard import CacheLeaf
from .stage_cache import (attend, attend_width, cache_update_and_read,
                          read_window)

# what a block step of either kind counts into the cache's `stats` leaf, in
# this order: the Mamba-2 layers' positions by form and `ssm_state_carries`
# (calls that took a convolution tail, and so a state, that is not zeros from
# the cache), the attention layers' spans that took the streaming kernel, and
# the stepped positions whose state the in-place kernel updated
# (`state_kernel_mode`)
STATS = ("ssm_positions_chunked", "ssm_positions_stepped",
         "ssm_state_carries") + decoder.ATTEND_STATS + ("ssm_steps_fused",)

# activations, state, tail, keys and values (module docstring, Precision)
ACTIVATIONS = jnp.float32
# products of two activations: float32 in full (the state is a sum over every
# position before it; q.k feeds a router's discrete choice two layers on)
_EXACT = jax.lax.Precision.HIGHEST


def conv_channels(cfg: TransformerConfig) -> int:
    """Channels of `xBC`, what the convolution runs over."""
    return cfg.ssm_heads * cfg.ssm_head_dim + 2 * cfg.ssm_groups * cfg.ssm_state


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """The cache's leaves (module docstring, Cache): what follows `[L, B,
    T]` in the "attention" blocks' `k`, `v` and `[L, B]` in the "mamba"
    blocks' state and tail, with the kind of block that owns each (both
    families name the kinds so)."""
    rows = CacheLeaf((cfg.kv_heads * cfg.head_dim,), ACTIVATIONS,
                     "attention")
    return {"k": rows, "v": rows,
            "ssm_state": CacheLeaf(
                (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                ACTIVATIONS, "mamba", whole=True),
            "ssm_conv": CacheLeaf(
                (cfg.conv_kernel - 1, conv_channels(cfg)), ACTIVATIONS,
                "mamba", whole=True)}


# -- Mamba-2 -------------------------------------------------------------------

def _dots(spec: str, x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.einsum(spec, x, y, precision=_EXACT,
                      preferred_element_type=jnp.float32)


def _decay(x: jax.Array) -> jax.Array:
    """exp(x) for x <= 0 where it is applied once: the chunked form's
    matrices (`decoder.exp_ulp` where it compounds)."""
    return 1.0 + jnp.expm1(x)


def ssm_step(x, bm, cm, dt, la, state):
    """One position of the recurrence (module docstring): x [B, G, R, P] (`R`
    heads a group), bm, cm [B, G, N], dt, la = log a [B, G, R], state [B, G,
    R, P, N], float32. Products on the vector unit, exact: a step reads the
    state and writes it, and has nothing for the MXU. -> (y without the skip
    [B, G, R, P], state)."""
    state = state * exp_ulp(la)[..., None, None] \
        + (dt[..., None] * x)[..., None] * bm[:, :, None, None, :]
    return jnp.sum(state * cm[:, :, None, None, :], axis=-1), state


def _kernel_mode():
    """How this backend runs the state kernel (`ops/ssm_step.py`): "mosaic"
    on a TPU, None where Mosaic cannot run (`ssm_step` serves every call);
    the tests put "interpret" here."""
    return "mosaic" if jax.default_backend() == "tpu" else None


def state_kernel_mode(bcache, span: int, prefill: bool):
    """How a Mamba-2 block's call moves its state on, read off the call:
    `_kernel_mode()` where the kernel updates the layer where it lies in the
    cache's stack (a step, over what the cache holds, of a float32 leaf that
    the decode driver has its blocks write in place, `LayerCache.placed`;
    compiled, a head's state of whole tiles: interpret mode knows none);
    None where `ssm_step` or `ssm_chunked` hand back the rows' state for the
    driver to write."""
    stack = bcache.stack["ssm_state"]
    mode = _kernel_mode()
    fits = span == 1 and not prefill and "ssm_state" in bcache.placed \
        and stack.dtype == jnp.float32 \
        and (mode == "interpret" or ssm_kernel.whole_tiles(*stack.shape[3:]))
    return mode if fits else None


def ssm_chunked(x, bm, cm, dt, la, state, chunk: int):
    """The recurrence over a span in chunks (module docstring): x [B, S, G,
    R, P], bm, cm [B, S, G, N], dt, la [B, S, G, R], state [B, G, R, P, N],
    float32. -> (y without the skip [B, S, G, R, P], the state after)."""
    b, s = x.shape[:2]
    n = -(-s // chunk)

    def lay(t):     # [B, S, ...] -> [n, B, C, ...], zeros past S
        t = jnp.pad(t, ((0, 0), (0, n * chunk - s)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((b, n, chunk) + t.shape[2:]), 1, 0)

    at = jnp.arange(chunk)
    lower = at[:, None] >= at[None, :]

    def one_chunk(state, xs):
        x_c, b_c, c_c, dt_c, la_c = xs
        # the running sum as a product in full float32 (module docstring)
        run = _dots("bsgr,cs->bgrc", la_c, lower.astype(la_c.dtype))
        within = jnp.where(lower, _decay(jnp.where(
            lower, run[..., :, None] - run[..., None, :], 0.0)), 0.0)
        scores = _dots("bcgk,bsgk->bgcs", c_c, b_c)[:, :, None] * within
        dtx = dt_c[..., None] * x_c                         # [B, C, G, R, P]
        y = _dots("bgrcs,bsgrp->bcgrp", scores, dtx) \
            + jnp.moveaxis(_decay(run), -1, 1)[..., None] \
            * _dots("bgrpk,bcgk->bcgrp", state, c_c)
        total = run[..., -1]                                # [B, G, R]
        left = jnp.moveaxis(_decay(total[..., None] - run), -1, 1)
        state = exp_ulp(total)[..., None, None] * state \
            + _dots("bcgrp,bcgk->bgrpk", left[..., None] * dtx, b_c)
        return state, y

    state, y = jax.lax.scan(one_chunk, state,
                            tuple(lay(t) for t in (x, bm, cm, dt, la)))
    y = jnp.moveaxis(y, 0, 1).reshape((b, n * chunk) + x.shape[2:])
    return y[:, :s], state


def _mamba_rows(p: Dict, normed, state, tail, cfg: TransformerConfig,
                in_place=None):
    """`mamba` of some rows of the batch, from their `state` [rows, H, P, N]
    and `tail` [rows, K - 1, channels]. -> (out, state, tail). A step with
    `in_place` (`mamba`) reads no `state` and hands back what that does."""
    b, s, _ = normed.shape
    h, hd, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, \
        cfg.ssm_groups
    inner = h * hd
    proj = lin(p["in_proj"], normed)
    z, xbc, dt = jnp.split(proj, [inner, inner + conv_channels(cfg)], axis=-1)
    mixed, tail = causal_conv(p["conv"], xbc, tail)
    xbc = jax.nn.silu(mixed + p["conv_bias"].astype(jnp.float32))
    x, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(b, s, g, h // g, hd)
    bm, cm = bm.reshape(b, s, g, n), cm.reshape(b, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    la = (-jnp.exp(p["a_log"].astype(jnp.float32)) * dt).reshape(
        b, s, g, h // g)
    dt = dt.reshape(b, s, g, h // g)
    if in_place is not None:
        state, y = in_place(exp_ulp(la[:, 0]).reshape(b, h),
                            (dt[:, 0, ..., None] * x[:, 0]).reshape(b, h, hd),
                            bm[:, 0], cm[:, 0])
        y = y.reshape(b, 1, g, h // g, hd)
    else:
        state = state.astype(jnp.float32).reshape(
            (b, g, h // g) + state.shape[2:])
        if s == 1:
            y, state = ssm_step(x[:, 0], bm[:, 0], cm[:, 0], dt[:, 0],
                                la[:, 0], state)
            y = y[:, None]
        else:
            y, state = ssm_chunked(x, bm, cm, dt, la, state,
                                   min(cfg.linear_chunk, s))
        state = state.reshape((b, h) + state.shape[3:])
    y = y + p["d_skip"].astype(jnp.float32).reshape(g, h // g, 1) * x
    # the gate goes in before the norm; a norm a group of heads
    y = y.reshape(b, s, g, -1) * jax.nn.silu(z).reshape(b, s, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg.layer_norm_eps)
    y = y.reshape(b, s, inner) * p["out_norm"].astype(jnp.float32)
    return lin(p["out"], y.astype(normed.dtype)), state, tail


def mamba(p: Dict, normed, read, cfg: TransformerConfig, in_place=None):
    """The Mamba-2 mixer of `normed` [B, S, D]. `read(name, first, rows)`
    hands the cache's `ssm_state` [rows, H, P, N] and `ssm_conv` [rows, K -
    1, channels] (the convolution's inputs at the positions before) of the
    requests `[first, first + rows)`. -> (out [B, S, D], state, tail) after
    the span. A step whose state the kernel updates where it lies
    (`state_kernel_mode`) brings `in_place(decay [B, H], dt x [B, H, P], B_t,
    C_t [B, G, N]) -> (the cache's stack, y [B, H, P])`: no state is read
    here, all rows go in one call, and `state` is that stack.

    The rows of the batch in groups whose input projection's three-pass
    product stays under `decoder.PRODUCT_BYTES` (`in_row_chunks`' rule, by
    whole requests: a request's chunks carry its state), each group's state
    read from the stack when its turn comes: at 128 rows a span of 128
    positions would hold 3.6 GB of projections, a gigabyte of decays a
    chunk and half a gigabyte of state copied out."""
    b, s, _ = normed.shape
    if in_place is not None:
        return _mamba_rows(p, normed, None, read("ssm_conv", 0, b), cfg,
                           in_place)
    groups = 1
    while b % (2 * groups) == 0 and b // groups * s * p["in_proj"].shape[0] \
            * 12 > decoder.PRODUCT_BYTES:
        groups *= 2
    rows = b // groups
    if groups == 1:
        return _mamba_rows(p, normed, read("ssm_state", 0, b),
                           read("ssm_conv", 0, b), cfg)
    out = jax.lax.map(
        lambda xs: _mamba_rows(p, xs[0], read("ssm_state", xs[1], rows),
                               read("ssm_conv", xs[1], rows), cfg),
        (normed.reshape((groups, rows) + normed.shape[1:]),
         jnp.arange(groups) * rows))
    return tuple(t.reshape((b,) + t.shape[2:]) for t in out)


def mamba_block(p: Dict, normed, bcache, cfg: TransformerConfig,
                prefill: bool):
    """A Mamba-2 block's mixer over the cache (a family's
    `cached_block_step`): its state and its convolution's tail from the
    cache (a prefill, at `pos` 0: zeros), and what they are after the span
    recorded as rows, which take their place; a step whose state the kernel
    has updated in the stack (`state_kernel_mode`) records no state and
    hands back the cache with that stack. -> (out, the cache, its rows,
    what the call counts under `STATS`)."""
    b, s, _ = normed.shape
    stack, layer = bcache.stack, bcache.layer
    mode = state_kernel_mode(bcache, s, prefill)

    def read(name, first, count):
        buf = stack[name]
        got = jax.lax.dynamic_slice(
            buf, (layer, first) + (0,) * (buf.ndim - 2),
            (1, count) + buf.shape[2:])[0]
        return jnp.zeros_like(got) if prefill else got

    counts = [b * s if s > 1 else 0, b if s == 1 else 0,
              jnp.any(read("ssm_conv", 0, b) != 0).astype(jnp.int32), 0,
              b if mode else 0]
    if mode:
        mixed, state, tail = mamba(
            p, normed, read, cfg, in_place=lambda *step: ssm_kernel.step(
                stack["ssm_state"], layer, *step,
                interpret=mode == "interpret"))
        return mixed, bcache._replace(stack=dict(stack, ssm_state=state)), \
            {"ssm_conv": tail}, counts
    mixed, state, tail = mamba(p, normed, read, cfg)
    return mixed, bcache, {"ssm_state": state, "ssm_conv": tail}, counts


# -- attention -----------------------------------------------------------------

def attention(p: Dict, normed, bcache, pos, cfg: TransformerConfig,
              prefill: bool, read_len=None, scale=None):
    """GQA without rotation of `normed` [B, S, D] at [pos, pos + S) over the
    cached window below `pos` and its own rows, the scores times `scale`
    (None: `Dh**-0.5`). -> (out, the cache with the rows k, v recorded, 1
    where the attention took the streaming kernel)."""
    b, s, _ = normed.shape
    heads, groups, hd = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    q = lin(p["q"]["w"], normed).reshape(b, s, heads, hd)
    if scale is not None:   # the products divide by the root of the width
        q = q * (scale * hd ** 0.5)
    k = lin(p["k"]["w"], normed).reshape(b, s, groups, hd)
    v = lin(p["v"]["w"], normed).reshape(b, s, groups, hd)
    if s == 1:      # a step: the window as stored, one softmax over both parts
        k, v, keep, bcache = cache_update_and_read(
            bcache, k, v, pos, prefill, s, normed.dtype, read_len=read_len)
        return lin(p["attn_out"]["w"],
                   attend(q, k, v, keep, cfg, precision=_EXACT)), bcache, 0
    k, v = k.reshape(b, s, -1), v.reshape(b, s, -1)
    stack = bcache.stack
    # through the cache's dtype, as if read back from it
    k = k.astype(stack["k"].dtype).astype(normed.dtype)
    v = v.astype(stack["v"].dtype).astype(normed.dtype)
    at = jnp.arange(s)
    keys, values = [by_head(k, groups)], [by_head(v, groups)]
    keeps = [(at[None, :] <= at[:, None])[None]]            # [1, Q, K] a part
    if not prefill:
        width = attend_width(bcache, read_len)
        lanes = [slice(grp * hd, (grp + 1) * hd) for grp in range(groups)]
        for name, parts in (("k", keys), ("v", values)):
            parts.insert(0, tuple(read_window(stack[name], bcache.layer,
                                              width, lane) for lane in lanes))
        keeps.insert(0, jnp.broadcast_to(jnp.arange(width) < pos,
                                         (1, s, width)))
    q = q.reshape(b, s, groups, heads // groups, hd)
    ctx, fused = zip(*(decoder.attend_masked(
        q[:, :, grp], [part[grp] for part in keys],
        [part[grp] for part in values], keeps) for grp in range(groups)))
    ctx = jnp.stack(ctx, axis=2).astype(normed.dtype).reshape(b, s, -1)
    return lin(p["attn_out"]["w"], ctx), \
        bcache._replace(rows={"k": k, "v": v}), fused[0]


# -- loading -------------------------------------------------------------------

def mamba_leaves(get, mix: str, cfg: TransformerConfig) -> Dict:
    """A Mamba-2 mixer's leaves from the tensors under `mix` (Mamba-2's
    published names: `{in_proj,out_proj}.weight`, `conv1d.{weight,bias}`,
    `A_log`, `D`, `dt_bias`, `norm.weight`); a family keeps `a_log`,
    `d_skip` and `dt_bias` float32, as published (`FLOAT32`)."""
    inner, channels = cfg.ssm_heads * cfg.ssm_head_dim, conv_channels(cfg)
    return dict(
        in_proj=get(mix + "in_proj.weight",
                    (inner + channels + cfg.ssm_heads, cfg.hidden_size)),
        conv=get(mix + "conv1d.weight",
                 (channels, 1, cfg.conv_kernel))[:, 0].T,
        conv_bias=get(mix + "conv1d.bias", (channels,)),
        a_log=get(mix + "A_log", (cfg.ssm_heads,)),
        d_skip=get(mix + "D", (cfg.ssm_heads,)),
        dt_bias=get(mix + "dt_bias", (cfg.ssm_heads,)),
        out_norm=get(mix + "norm.weight", (inner,)),
        out=get(mix + "out_proj.weight", (cfg.hidden_size, inner)))


def attention_leaves(get, mix: str, cfg: TransformerConfig) -> Dict:
    """An attention's leaves from `{q,k,v,o}_proj.weight` under `mix`."""
    d, heads, groups, hd = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.kv_heads, cfg.head_dim
    return dict(
        q={"w": get(mix + "q_proj.weight", (heads * hd, d))},
        k={"w": get(mix + "k_proj.weight", (groups * hd, d))},
        v={"w": get(mix + "v_proj.weight", (groups * hd, d))},
        attn_out={"w": get(mix + "o_proj.weight", (d, heads * hd))})


# the leaves of `mamba_leaves` that stay float32 (`decoder.on_device`)
FLOAT32 = (("a_log",), ("d_skip",), ("dt_bias",))


def undrawn(key: str, shape: tuple):
    """What `init_params` does not draw of a Mamba-2 mixer: `D` ones, and
    the decays spread over the heads as Mamba-2's initialisation spreads
    them (`A` from 1 to 16, `dt` log-uniform over its published range)."""
    if key.endswith(".D"):
        return np.ones(shape, np.float32)
    if key.endswith("A_log"):
        return np.log(np.linspace(1.0, 16.0, shape[0], dtype=np.float32))
    if key.endswith("dt_bias"):     # softplus^-1 of dt
        dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), shape[0]))
        return np.log(np.expm1(dt)).astype(np.float32)
    return None
