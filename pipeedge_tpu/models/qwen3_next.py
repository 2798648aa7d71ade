"""Qwen3-Next (`model_type` qwen3_next): blocks in periods of four, three
Gated DeltaNet layers (linear attention over a state that is a matrix a
head) and one gated full-attention layer, each before an expert layer of
many small experts beside a shared one that a learned sigmoid gates.

The block, `x` [B, S, D], no bias anywhere. `rms` is the family's
ZERO-CENTRED norm, `x * rsqrt(mean(x^2) + eps) * (1 + w)` in float32:
  h = x + Mixer(rms(x));  x' = h + MoE(rms(h))
Block i is full attention where `(i + 1) % full_attention_interval == 0`
(`block_kind`); the two kinds have different leaves, so a stage holds them
as runs (models/shard.py `BlockRuns`).

**Gated DeltaNet.** `m = [q | k | v]` (16 + 16 key heads and 32 value heads
of 128: 8,192 channels) goes through a depthwise causal convolution four
wide and SiLU; `q`, `k` are l2-normalised a head, repeated to the value
heads, `q` scaled by `Dk**-0.5`. A value head: `beta_t = sigmoid(b_t)`, `g_t
= -exp(A_log) * softplus(a_t + dt_bias)`, and the state `S` [Dk, Dv]:
  S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
  o_t = S^T q_t
(`delta_step`, what a decode step runs). The output goes through a norm a
head gated by `silu(z)` and `out_proj`. A span of a prompt runs the same in
chunks of `C` = `cfg.linear_chunk` (`delta_chunked`): with `gamma` the
running sum of `g` in a chunk and `Gamma_ts = exp(gamma_t - gamma_s)`,
  A = (I + strict_lower((beta K) K^T * Gamma))^-1
  D = A (beta V) - A (beta exp(gamma) K) S_0          (the chunk's d)
  O = (Q exp(gamma)) S_0 + lower(Q K^T * Gamma) D
  S_C = exp(gamma_C) S_0 + (K exp(gamma_C - gamma))^T D
everything that does not need `S_0` for all chunks at once, then one scan
over the chunks that carries the state. `A` is forward substitution by
blocks (`_inverse_unit_lower`). A last chunk that the span does not fill is
padded with `beta` = 0 and `g` = 0, which leaves the state as it was.

**Gated full attention.** A head of `q_proj` is `[query | gate]`; q and k
are normed a head (zero-centred), the first `partial_rotary_factor` of a
head's width is rotated (halves layout), GQA, causal softmax in float32; the
heads' outputs are multiplied by `sigmoid(gate)` before `o_proj`.

**Cache: two geometries** (`cache_leaves`, models/shard.py `CacheLeaf`).
The full layers own `k`, `v` `[L_full, B, T, G*Dh]`, a row a position,
written at `pos` and read as a window. The linear layers own `gdn_state`
`[L_linear, B, Hv, Dk, Dv]` and `gdn_conv` `[L_linear, B, 3, 8192]` (the
convolution's last three inputs), a row a REQUEST, read and replaced whole
by every call: a span takes its initial state from there and leaves its
final state there. No layer holds the other kind's leaves: at 8 rows x
32,768 positions in float32 that is 1.07 GB of keys and values in the one
full layer of four and 6.6 MB a row of state in the three others, where
four layers of keys and values would be 4.3 GB.

**Precision.** Weights as stored (bfloat16); activations, cache and state
float32: products with weights through `exact_dot`, the delta rule's
products of two activations at `HIGHEST`, the delta rule's (the state is a
sum over every position before) and the attention's (`_STATE` says what
`HIGH` did); a step's and the chunk's inverse's are float32 multiplications
and sums on the vector unit, which is what `HIGHEST` stands in for. The
router's top-10 of 512 is a discrete choice that a bfloat16 computation
makes differently from the float32 reference.

**Prefill** runs in spans of `cfg.prefill_chunk` positions (a multiple of
the chunk) through the decode-shaped stage program, as keye's does.

Refused by name: the forward path (`sublayer`), tp, sp and ep meshes, the
int8 cache, `--kv-pages` (a page holds positions) and speculative verify (a
rejected draft would need the state of an earlier position).

Weight format: the published state dict (`model.layers.N.linear_attn.
{in_proj_qkvz, in_proj_ba, conv1d, out_proj}.weight`, `.linear_attn.{dt_bias,
A_log, norm.weight}`; `.self_attn.{q,k,v,o}_proj.weight`, `.self_attn.{q,k}_
norm.weight`; `.mlp.gate.weight`, `.mlp.experts.E.*`, `.mlp.shared_expert.*`,
`.mlp.shared_expert_gate.weight`). `in_proj_qkvz` and `in_proj_ba` keep
their rows grouped by key head and `q_proj` by head; the loader sorts the
rows once into `[q | k | v]`, `z`, `[b | a]` and `query`, `gate`, so the
program splits nothing.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import ShardConfig, decoder
from ..telemetry import metrics as prom
from .decoder import by_head, in_row_chunks, lin
from .layers import TransformerConfig, causal_conv, rope_rotate
from .shard import CacheLeaf, FamilySpec
from .stage_cache import attend_width, read_window

# what a block step counts into the cache's `stats` leaf, in this order
STATS = decoder.MOE_STATS + ("gdn_positions_chunked", "gdn_positions_stepped",
                             "gdn_state_carries") + decoder.ATTEND_STATS

# activations, cache and state (module docstring, Precision)
ACTIVATIONS = jnp.float32
# products of two activations: float32 in full, the delta rule's (the state
# is a sum over every position before it) and the attention's, which are
# `decoder.attend_masked`'s (`HIGHEST` in the einsums and the kernel). At `HIGH`
# (three bfloat16 passes, about 16 bits) the first chip run's greedy tokens
# lay up to 0.86% of the logits' range from the reference's (PERF.md, PR 33):
# q and k are normed, so scores are of order 1 and 1e-4 off, and the router's
# top-10 of 512 after them is a discrete choice that amplifies it (keye's
# attention found the same, PR 27)
_STATE = jax.lax.Precision.HIGHEST

# the widest diagonal block of a chunk's triangular matrix that is inverted a
# row at a time (`inverse_block`); wider ones are merged from two
_INVERSE_BLOCK = 16

# /metrics plane: which form of `_inverse_unit_lower` a built stage's chunks
# take. Set when a stage's parameters are assembled, from the chunk alone
_M_INVERSE_BLOCK = prom.REGISTRY.gauge(
    "pipeedge_gdn_inverse_block",
    "width of the diagonal blocks the chunked delta rule's triangular "
    "inverse takes a row at a time before it merges them, by chunk; 0 = the "
    "chunk is one block, plain rows")


def prefill_span(cfg: TransformerConfig) -> int:
    return cfg.prefill_chunk


def block_kind(cfg: TransformerConfig, block_id: int) -> str:
    return "full" if (block_id + 1) % cfg.full_attention_interval == 0 \
        else "linear"


def conv_channels(cfg: TransformerConfig) -> int:
    """Channels of `m = [q | k | v]`, what the convolution runs over."""
    return 2 * cfg.linear_key_heads * cfg.linear_key_dim \
        + cfg.linear_value_heads * cfg.linear_value_dim


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """The cache's leaves (module docstring, Cache): what follows `[L, B,
    T]` in the full layers' `k`, `v` and `[L, B]` in the linear layers'
    state, with the kind of block that owns each."""
    rows = CacheLeaf((cfg.kv_heads * cfg.head_dim,), ACTIVATIONS, "full")
    return {"k": rows, "v": rows,
            "gdn_state": CacheLeaf(
                (cfg.linear_value_heads, cfg.linear_key_dim,
                 cfg.linear_value_dim), ACTIVATIONS, "linear", whole=True),
            "gdn_conv": CacheLeaf(
                (cfg.linear_conv_kernel - 1, conv_channels(cfg)),
                ACTIVATIONS, "linear", whole=True),
            "stats": jax.ShapeDtypeStruct((len(STATS),), jnp.int32)}


def rms(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """The zero-centred norm: the stored weight is what is added to 1."""
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                + eps)
    return (normed * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def partial_rotate(x: jax.Array, pos: jax.Array,
                   cfg: TransformerConfig) -> jax.Array:
    """x [B, S, H, Dh] at positions `pos` [S]: the first
    `partial_rotary_factor` of the head's width turned (`rope_rotate`: halves
    layout, frequencies for that width), the rest as it is."""
    turned = int(x.shape[-1] * cfg.partial_rotary_factor)
    head, rest = jnp.split(x, [turned], axis=-1)
    return jnp.concatenate([rope_rotate(head, pos, cfg.rope_theta), rest],
                           axis=-1)


# -- the gated delta rule ----------------------------------------------------

# a decay's exp where it is applied a position (a chunk) after another
_exp = decoder.exp_ulp


def _decay(x: jax.Array) -> jax.Array:
    """exp(x) for x <= 0 where it is applied once and `_exp`'s thirty
    operations a value would show: the chunked form's matrices."""
    return 1.0 + jnp.expm1(x)


def delta_step(q, k, v, beta, g, state):
    """One position of the recurrence (module docstring): q, k [B, H, Dk],
    v [B, H, Dv], beta, g [B, H], state [B, H, Dk, Dv], float32. Products
    on the vector unit, exact: a step reads the state and writes it, and
    has nothing for the MXU. -> (o [B, H, Dv], state)."""
    state = state * _exp(g)[..., None, None]
    d = beta[..., None] * (v - jnp.sum(state * k[..., None], axis=-2))
    state = state + k[..., None] * d[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def inverse_block(chunk: int) -> int:
    """The width of the diagonal blocks that `_inverse_unit_lower` inverts
    a row at a time in a chunk of `chunk`: `chunk` halved while it is even
    and above `_INVERSE_BLOCK` (64 -> 16, 24 -> 12, 16 and under as they
    are), so that blocks of that width merge upward in pairs to the whole."""
    block = chunk
    while block > _INVERSE_BLOCK and block % 2 == 0:
        block //= 2
    return block


def _inverse_unit_lower(low: jax.Array) -> jax.Array:
    """(I + low)^-1 of strictly lower triangular `low` [..., C, C], by
    forward substitution, in blocks. The diagonal blocks of
    `inverse_block(C)`, all of them in one array, a row at a time: row i of
    a block's inverse's strict part is `-low_i - low_i X` over the rows
    above, which are final. Then neighbours merge upward until one block is
    the whole (16 -> 32 -> 64):
      [[A, 0], [L, B]]^-1 = [[A^-1, 0], [-B^-1 L A^-1, B^-1]]
    with `L` the block of `low` below A and beside B. That is the same
    substitution with blocks for entries, every term of it computed once.
    It is NOT a series in powers of `low`: keys of a trained model lie
    close together in a chunk, and the powers' terms then cancel from 1e18
    down. A chunk no wider than one block (the tests' 4) is the plain row
    substitution and merges nothing: one algorithm, its one parameter read
    off the shape.

    The matrices of a span (4,096 of 64 x 64 in the cell) lie on the minor
    axis throughout, `[C, C, M]`: a row's step and a merge's products are
    then float32 multiplications and sums over whole lanes on the vector
    unit, exact as `delta_step`'s are, with no axis of 16 or 64 padded to a
    lane tile and no row written as a tile of eight. Over `[..., C, C]` as
    it comes, every trip of a row loop sweeps the whole array from HBM, its
    64 lanes padded to 128 (134 MB in the cell; 63 trips were 1.78 s of a
    prefill's 10.7: PERF.md, PR 36)."""
    c = low.shape[-1]
    block = inverse_block(c)
    x = jnp.moveaxis(low.reshape((-1, c, c)), 0, -1)            # [C, C, M]

    def tiles(down):    # `low`'s blocks `down` below the diagonal, every
        # (1 + down)th: [tiles, row, column, M]
        grid = x.reshape(c // block, block, c // block, block, -1)
        return jnp.stack([grid[j + down, :, j]
                          for j in range(0, c // block, 1 + down)])

    def row(i, d):
        mine = jax.lax.dynamic_slice_in_dim(d, i, 1, axis=1)
        mine = mine + jnp.sum(jnp.swapaxes(mine, 1, 2) * d, axis=1,
                              keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(d, mine, i, axis=1)

    def times(a, b):    # [P, r, k, M] x [P, k, c, M] -> [P, r, c, M]
        return jnp.sum(a[:, :, :, None] * b[:, None], axis=2)

    inverse = jax.lax.fori_loop(1, block, row, -tiles(0)) \
        + jnp.eye(block, dtype=low.dtype)[:, :, None]
    while block < c:
        # neighbours by a reshape: a slice of every second block compiles
        # to a gather on the chip
        pairs = inverse.reshape((-1, 2) + inverse.shape[1:])
        first, second = pairs[:, 0], pairs[:, 1]
        corner = -times(times(second, tiles(1)), first)
        inverse = jnp.concatenate(
            [jnp.concatenate([first, jnp.zeros_like(first)], axis=2),
             jnp.concatenate([corner, second], axis=2)], axis=1)
        block *= 2
    return jnp.moveaxis(inverse[0], -1, 0).reshape(low.shape)


def delta_chunked(q, k, v, beta, g, state, chunk: int):
    """The recurrence over a span in chunks (module docstring): q, k [B, S,
    H, Dk], v [B, S, H, Dv], beta, g [B, S, H], state [B, H, Dk, Dv], all
    float32. -> (o [B, S, H, Dv], the state after the span)."""
    b, s, h, _ = q.shape
    n = -(-s // chunk)

    def lay(x):     # [B, S, H, ...] -> [N, B, H, C, ...], zeros past S
        x = jnp.pad(x, ((0, 0), (0, n * chunk - s)) + ((0, 0),)
                    * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]),
                            (1, 3), (0, 2))

    def dots(spec, x, y):
        return jnp.einsum(spec, x, y, precision=_STATE,
                          preferred_element_type=jnp.float32)

    q, k, v, beta, g = (lay(x) for x in (q, k, v, beta, g))
    at = jnp.arange(chunk)
    lower = at[:, None] >= at[None, :]
    # the running sum as a product in full float32: the chip runs a
    # `cumsum` as one bfloat16 pass, which left gamma 1e-3 off and with it
    # the state a prefill hands to the steps (PERF.md, PR 33)
    gamma = dots("nbhs,cs->nbhc", g, lower.astype(g.dtype))  # [N, B, H, C]
    decay = jnp.where(lower, _decay(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], 0.0)), 0.0)
    k_beta = k * beta[..., None]
    inverse = _inverse_unit_lower(
        jnp.where(at[:, None] > at[None, :],
                  dots("nbhck,nbhsk->nbhcs", k_beta, k) * decay, 0.0))
    u = dots("nbhcs,nbhsv->nbhcv", inverse, v * beta[..., None])
    w = dots("nbhcs,nbhsk->nbhck", inverse,
             k_beta * _decay(gamma)[..., None])
    within = dots("nbhck,nbhsk->nbhcs", q, k) * decay
    q_in = q * _decay(gamma)[..., None]
    k_out = k * _decay(gamma[..., -1:] - gamma)[..., None]
    kept = _exp(gamma[..., -1])                             # [N, B, H]

    def one_chunk(carry, xs):
        u_n, w_n, within_n, q_n, k_n, kept_n = xs
        d = u_n - dots("bhck,bhkv->bhcv", w_n, carry)
        o = dots("bhck,bhkv->bhcv", q_n, carry) \
            + dots("bhcs,bhsv->bhcv", within_n, d)
        return kept_n[..., None, None] * carry \
            + dots("bhck,bhcv->bhkv", k_n, d), o

    state, o = jax.lax.scan(one_chunk, state,
                            (u, w, within, q_in, k_out, kept))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * chunk, h, -1)
    return o[:, :s], state


def gated_delta_net(p: Dict, normed, state, tail, cfg: TransformerConfig):
    """The linear mixer of `normed` [B, S, D] from `state` [B, Hv, Dk, Dv]
    and the convolution's `tail` [B, K - 1, channels] (its inputs at the
    positions before). -> (out [B, S, D], state, tail) after the span."""
    b, s, _ = normed.shape
    hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    eps = cfg.layer_norm_eps
    m = in_row_chunks(lambda rows: lin(p["in_m"], rows), normed,
                      p["in_m"].shape[0])
    z = lin(p["in_z"], normed).reshape(b, s, hv, dv)
    ba = lin(p["in_ba"], normed).astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., hv:] + p["dt_bias"].astype(jnp.float32))
    mixed, tail = causal_conv(p["conv"], m, tail)           # [K, channels]
    q, k, v = jnp.split(jax.nn.silu(mixed), [hk * dk, 2 * hk * dk], axis=-1)

    def heads(x, scale):    # l2 norm a key head, then a copy a value head
        x = x.reshape(b, s, hk, dk)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        return jnp.repeat(x * scale, hv // hk, axis=2)

    q, k, v = heads(q, dk ** -0.5), heads(k, 1.0), v.reshape(b, s, hv, dv)
    if s == 1:
        o, state = delta_step(q[:, 0], k[:, 0], v[:, 0], beta[:, 0],
                              g[:, 0], state)
        o = o[:, None]
    else:
        o, state = delta_chunked(q, k, v, beta, g, state, cfg.linear_chunk)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * p["out_norm"].astype(jnp.float32) * jax.nn.silu(z)
    return lin(p["out"], o.reshape(b, s, -1).astype(normed.dtype)), \
        state, tail


# -- the gated full attention ------------------------------------------------

def _attend_chunk(q, parts, first, pos):
    """Context [B, Q, H*Dh] of the queries q [B, Q, H, Dh] that sit `first`
    rows into the span at `pos`. `parts`: (k, v: one [B, K, Dh] a KV head;
    own: the part is the span's rows, causal, else cached rows, live below
    `pos`). One softmax over all parts, a KV group at a time
    (`decoder.attend_masked`). -> (context, 1 where the streaming kernel
    ran)."""
    b, n_q, h, hd = q.shape
    groups = len(parts[0][0])
    q = q.reshape(b, n_q, groups, h // groups, hd)
    keeps = []      # one mask for every row of the batch: [1, Q, K] a part
    for k, _, own in parts:
        at = jnp.arange(k[0].shape[1])
        keep = at[None, :] <= first + jnp.arange(n_q)[:, None] if own \
            else jnp.broadcast_to(at < pos, (n_q, at.shape[0]))
        keeps.append(keep[None])
    out, fused = zip(*(decoder.attend_masked(
        q[:, :, grp], [k[grp] for k, _, _ in parts],
        [v[grp] for _, v, _ in parts], keeps) for grp in range(groups)))
    return jnp.stack(out, axis=2).astype(q.dtype).reshape(b, n_q, h * hd), \
        fused[0]


def attend(q, parts, pos):
    """`_attend_chunk` over all queries [B, Q, H, Dh]: in one call where a
    KV group's call takes the streaming kernel (`decoder.kernel_mode`: no
    score leaves VMEM), else in chunks of queries whose scores (one KV
    group's) stay under `decoder.SCORE_BYTES`."""
    b, n_q, h, hd = q.shape
    per_group = h // len(parts[0][0])       # query heads a KV group
    part_keys = [part[0][0].shape[1] for part in parts]
    chunk = n_q if decoder.kernel_mode(n_q * per_group, hd, q.dtype,
                                       part_keys) \
        else decoder.query_chunk(n_q, b * per_group * sum(part_keys) * 4)
    if chunk == n_q:
        return _attend_chunk(q, parts, 0, pos)
    # not `decoder.map_query_chunks`: a chunk's causal mask asks how many
    # rows into the span the chunk starts. A chunk has fewer rows than the
    # span, so no chunk takes the kernel where the span did not
    return decoder.join_queries(jax.lax.map(
        lambda xs: _attend_chunk(xs[0], parts, xs[1], pos)[0],
        (decoder.split_queries(q, chunk),
         jnp.arange(n_q // chunk) * chunk))), 0


def gated_attention(p: Dict, normed, bcache, pos, cfg: TransformerConfig,
                    prefill: bool, read_len=None):
    """The full mixer of `normed` [B, S, D] at [pos, pos + S) over the
    cached window below `pos` and its own rows. -> (out, the rows k, v
    [B, S, G*Dh] for the cache, 1 where the attention took the streaming
    kernel)."""
    b, s, _ = normed.shape
    eps, groups = cfg.layer_norm_eps, cfg.kv_heads
    q_pos = jnp.asarray(pos) + jnp.arange(s)
    q = in_row_chunks(lambda rows: lin(p["q"]["w"], rows), normed,
                      p["q"]["w"].shape[0])
    q = q.reshape(b, s, cfg.num_attention_heads, -1)
    gate = lin(p["gate"]["w"], normed)
    k = lin(p["k"]["w"], normed).reshape(b, s, groups, -1)
    v = lin(p["v"]["w"], normed)
    q = partial_rotate(rms(p["q_norm"], q, eps), q_pos, cfg)
    k = partial_rotate(rms(p["k_norm"], k, eps), q_pos, cfg).reshape(b, s, -1)
    stack = bcache.stack
    # through the cache's dtype, as if read back from it
    k = k.astype(stack["k"].dtype).astype(normed.dtype)
    v = v.astype(stack["v"].dtype).astype(normed.dtype)
    parts = [(by_head(k, groups), by_head(v, groups), True)]
    if not prefill:
        width = attend_width(bcache, read_len)
        lanes = [slice(grp * cfg.head_dim, (grp + 1) * cfg.head_dim)
                 for grp in range(groups)]
        parts.insert(0, tuple(
            tuple(read_window(stack[name], bcache.layer, width, head)
                  for head in lanes) for name in ("k", "v")) + (False,))
    ctx, fused = attend(q, parts, pos)
    return lin(p["attn_out"]["w"], ctx * jax.nn.sigmoid(gate)), k, v, fused


# -- the family's hooks --------------------------------------------------------

def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """Cached block (the decode driver's `_block_step` contract) of either
    kind. The rows of `x` sit at [pos, pos + S). A full block attends the
    cached window below `pos` and its own rows and records their keys and
    values for `write_rows`; a linear block takes its state and its
    convolution's tail from the cache (a prefill, at `pos` 0: zeros) and
    records what they are after the span, which takes their place."""
    b, s, _ = x.shape
    eps = cfg.layer_norm_eps
    normed = rms(p["ln_before"], x, eps)
    if "in_m" in p:
        stack = bcache.stack
        state, tail = (jax.lax.dynamic_index_in_dim(
            stack[name], bcache.layer, 0, keepdims=False)
            for name in ("gdn_state", "gdn_conv"))
        if prefill:
            state, tail = jnp.zeros_like(state), jnp.zeros_like(tail)
        mixed, state, tail = gated_delta_net(
            p, normed, state.astype(jnp.float32), tail, cfg)
        rows = {"gdn_state": state, "gdn_conv": tail}
        counts = jnp.array([b * s if s > 1 else 0, b if s == 1 else 0,
                            0 if prefill else 1, 0], jnp.int32)
    else:
        mixed, k, v, fused = gated_attention(p, normed, bcache, pos, cfg,
                                             prefill, read_len)
        rows = {"k": k, "v": v}
        counts = jnp.array([0, 0, 0, fused], jnp.int32)
    h = x + mixed
    delta, moe = decoder.routed_experts(p, rms(p["ln_after"], h, eps), cfg)
    rows["stats"] = jnp.concatenate(
        [moe.astype(jnp.int32), jnp.ones(1, jnp.int32), counts])
    return h + delta, bcache._replace(rows=rows)


# the head follows the zero-centred norm; positions live in the rotation and
# in the state
FAMILY = FamilySpec(name="qwen3_next", cached_block_step=cached_block_step,
                    **decoder.token_hooks("qwen3_next", ACTIVATIONS, rms),
                    decoder_model=True, position_dependent_attention=True,
                    cache_leaves=cache_leaves, prefill_span=prefill_span,
                    whole_leaves=("experts",), stats_names=STATS,
                    block_kind=block_kind)


# -- loading -------------------------------------------------------------------

def _assemble(cfg: TransformerConfig, shard_config: ShardConfig, get,
              dtype) -> Dict:
    """Shard params from `get(key, shape)`, a tensor of the published
    scheme (module docstring; `decoder.loader`, `assemble_shard`)."""
    d, heads, groups, hd = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.kv_heads, cfg.head_dim
    block = inverse_block(cfg.linear_chunk)
    _M_INVERSE_BLOCK.set(block if block < cfg.linear_chunk else 0,
                         chunk=str(cfg.linear_chunk))
    hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    per = hv // hk          # value heads a key head
    f = cfg.moe_intermediate_size
    first, count = cfg.held_experts or (0, cfg.n_experts)

    def mlp(root, width):
        return {"gate": get(root + "gate_proj.weight", (width, d)),
                "up": get(root + "up_proj.weight", (width, d)),
                "down": get(root + "down_proj.weight", (d, width))}

    def get_embed() -> Dict:
        return {"wte": get("model.embed_tokens.weight",
                           (cfg.vocab_size, d))}

    def linear_mixer(root: str) -> Dict:
        # rows grouped by key head: [q Dk | k Dk | v per*Dv | z per*Dv]
        qkvz = get(root + "in_proj_qkvz.weight",
                   (2 * hk * dk + 2 * hv * dv, d)).reshape(hk, -1, d)
        q, k, v, z = (part.reshape(-1, d) for part in (
            qkvz[:, :dk], qkvz[:, dk:2 * dk],
            qkvz[:, 2 * dk:2 * dk + per * dv], qkvz[:, 2 * dk + per * dv:]))
        # and [b per | a per]
        ba = get(root + "in_proj_ba.weight", (2 * hv, d)).reshape(hk, -1, d)
        cat = np.concatenate if isinstance(q, np.ndarray) else jnp.concatenate
        return {"in_m": cat([q, k, v]), "in_z": z,
                "in_ba": cat([ba[:, :per].reshape(-1, d),
                              ba[:, per:].reshape(-1, d)]),
                "conv": get(root + "conv1d.weight", (
                    2 * hk * dk + hv * dv, 1, cfg.linear_conv_kernel)
                    )[:, 0].T,
                "a_log": get(root + "A_log", (hv,)),
                "dt_bias": get(root + "dt_bias", (hv,)),
                "out_norm": get(root + "norm.weight", (dv,)),
                "out": get(root + "out_proj.weight", (d, hv * dv))}

    def full_mixer(root: str) -> Dict:
        # a head of q_proj: [query Dh | gate Dh]
        q = get(root + "q_proj.weight", (2 * heads * hd, d)).reshape(
            heads, 2, hd, d)
        return {"q": {"w": q[:, 0].reshape(-1, d)},
                "gate": {"w": q[:, 1].reshape(-1, d)},
                "k": {"w": get(root + "k_proj.weight", (groups * hd, d))},
                "v": {"w": get(root + "v_proj.weight", (groups * hd, d))},
                "q_norm": get(root + "q_norm.weight", (hd,)),
                "k_norm": get(root + "k_norm.weight", (hd,)),
                "attn_out": {"w": get(root + "o_proj.weight",
                                      (d, heads * hd))}}

    def get_block(block_id: int, subs: tuple) -> Dict:
        decoder.whole_blocks("qwen3_next", subs)
        root = f"model.layers.{block_id}."
        p = full_mixer(root + "self_attn.") \
            if block_kind(cfg, block_id) == "full" \
            else linear_mixer(root + "linear_attn.")
        p["ln_before"] = get(root + "input_layernorm.weight", (d,))
        p["ln_after"] = get(root + "post_attention_layernorm.weight", (d,))
        p["router"] = {"w": get(root + "mlp.gate.weight",
                                (cfg.n_experts, d)).T}
        held = [mlp(f"{root}mlp.experts.{e}.", f)
                for e in range(first, first + count)]
        p["experts"] = {name: decoder.stack([one[name] for one in held])
                        for name in ("gate", "up", "down")}
        p["shared"] = mlp(root + "mlp.shared_expert.",
                          f * cfg.n_shared_experts)
        p["shared_gate"] = get(root + "mlp.shared_expert_gate.weight",
                               (1, d))
        return p

    def get_final() -> Dict:
        return {"ln": get("model.norm.weight", (d,)),
                "head": {"w": get("lm_head.weight", (cfg.vocab_size, d))}}

    return decoder.assemble_shard(
        shard_config, get_embed, get_block, get_final, dtype,
        kind=lambda block_id: block_kind(cfg, block_id))


def _undrawn(key: str, shape: tuple):
    """What `init_params` does not draw: the gated norm's weights about 1
    (the zero-centred norms' are drawn, about 0), and the decays spread
    over the heads from a half to nearly one a position."""
    if key.endswith("linear_attn.norm.weight"):
        return np.ones(shape, np.float32)
    if key.endswith("A_log"):   # exp(g) = 2**-exp(A_log) at a = 0
        return np.linspace(-6.5, 0.0, shape[0], dtype=np.float32)
    if key.endswith("dt_bias"):
        return np.zeros(shape, np.float32)
    return None


load_params, init_params = decoder.loader(_assemble, _undrawn)
