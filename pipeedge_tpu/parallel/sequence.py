"""Sequence/context parallelism: ring attention and Ulysses-style all-to-all.

NEW capability beyond the reference: PipeEdge only ever splits the layer axis
and tops out at 512 tokens (SURVEY.md §5.7 — no ring/blockwise/Ulysses
anywhere). For long contexts the sequence axis must shard across chips; this
module provides both standard formulations, built on XLA collectives over a
`shard_map` mesh axis so the communication rides ICI:

- `ring_attention`: each chip holds a query/key/value sequence chunk; K/V
  chunks rotate around the ring via `lax.ppermute` while a streaming
  (log-sum-exp) softmax accumulates partial attention — memory per chip is
  O(S/n * S/n) for scores, O(S/n) for state, so sequence length scales
  linearly with chip count. Compute of block t overlaps the transfer of
  block t+1 (XLA schedules the ppermute asynchronously). With a sliding
  window the ring stops early: K/V blocks wholly behind the window are
  never rotated in, so a 4k-window/128k-prompt prefill does ~window/S of
  the full-causal work.
- `ulysses_attention`: all-to-all swaps sequence sharding for head sharding,
  runs blockwise local attention per head group (streaming softmax over
  S/n-sized key blocks — no [S, S] score materialization), and swaps back.
  Cheaper collectives when heads >= chips; per-chip score memory matches
  ring's O(H * (S/n)^2).

Both are exact (match full attention to float tolerance) and support causal
masking with global position offsets, plus Mistral-style sliding windows
(position q attends to k in (q - window, q], models/llama.py::_window_keep
semantics).
"""
from __future__ import annotations

import logging
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils import jax_compat
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

_WARNED_GQA_FALLBACK = set()


def _block_attention(q, k, v, m_prev, l_prev, acc_prev, q_offset, k_offset,
                     causal: bool, scale: float,
                     window: Optional[int] = None):
    """One streaming-softmax block update.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D] — or [B, Sk, KV, D] with KV < H
    (GQA): the kv heads repeat LOCALLY here, so ring_attention's
    ppermutes carry only the unrepeated rows (H/KV times fewer
    inter-chip bytes). Running (max, sum, acc) over the key axis;
    scores/stats in float32 regardless of input dtype. `window` bounds
    how far back a query attends: k in (q - window, q].
    """
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal or window is not None:
        sq, sk = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(sq)
        k_pos = k_offset + jnp.arange(sk)
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    m_blk = jnp.max(scores, axis=-1)                       # [B, H, Sq]
    m_new = jnp.maximum(m_prev, m_blk)
    # guard fully-masked rows (m == -inf) against NaN from exp(-inf - -inf)
    safe_m = jnp.where(jnp.isinf(m_new), 0.0, m_new)
    p = jnp.exp(jnp.where(jnp.isinf(scores), -jnp.inf, scores) -
                safe_m[..., None])
    corr = jnp.exp(jnp.where(jnp.isinf(m_prev), -jnp.inf, m_prev) - safe_m)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc_prev * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def _finish_softmax(acc, l, out_dtype):
    """Normalize the streaming accumulator; fully-masked rows output 0."""
    l = jnp.where(l == 0, 1.0, l)
    return (acc / l.transpose(0, 2, 1)[..., None]).astype(out_dtype)


def _check_window(causal: bool, window: Optional[int]) -> None:
    if window is not None:
        if not causal:
            raise ValueError("sliding window requires causal attention")
        if window < 1:
            raise ValueError(f"sliding window must be >= 1, got {window}")


def _ring_steps(n: int, chunk: int, window: Optional[int]) -> int:
    """How many ring rotations a windowed causal attention needs.

    Ring step t delivers the K/V block t hops behind the local queries;
    its nearest key is (t-1)*chunk + 1 positions before the first query,
    so any step with that distance > window - 1 is wholly outside every
    query's (q - window, q] range and is skipped — neither computed nor
    rotated in (the sliding-window point: a 4k-window prefill over a
    128k prompt does ~window/S of the full-causal ring work).
    """
    if window is None:
        return n
    return min(n, (window - 2) // chunk + 2)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str,
                   causal: bool = False,
                   window: Optional[int] = None) -> jax.Array:
    """Exact attention over a ring-sharded sequence axis.

    Call inside `shard_map` with q/k/v local chunks [B, S/n, H, D] sharded on
    the sequence axis `axis_name`. Returns the local output chunk.

    `window` (static int) applies the sliding-window mask AND shortens the
    ring: only the first ceil-enough steps whose K/V block can intersect
    some query's (q - window, q] range run at all; blocks wholly outside
    every window are skipped — never computed, never rotated in.
    """
    _check_window(causal, window)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    chunk = k.shape[1]
    # GQA: k/v may carry fewer heads than q — they rotate unrepeated
    # (repeat happens inside the block update), so the ring traffic is
    # sized by the kv heads, preserving GQA's bandwidth advantage
    perm = [(i, (i + 1) % n) for i in range(n)]

    n_steps = _ring_steps(n, chunk, window)

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, h, d), jnp.float32)
    q_offset = idx * sq

    def attend(t, m, l, acc, k_cur, v_cur):
        # K/V block t originated on ring neighbor (idx - t) mod n
        k_offset = ((idx - t) % n) * chunk
        return _block_attention(q, k_cur, v_cur, m, l, acc, q_offset,
                                k_offset, causal, scale, window)

    def step(t, carry):
        m, l, acc, k_cur, v_cur = carry
        m, l, acc = attend(t, m, l, acc, k_cur, v_cur)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return m, l, acc, k_nxt, v_nxt

    # the last block update runs OUTSIDE the loop so the ring does exactly
    # n_steps - 1 rotations: the step after the final attend would only
    # rotate in the first skipped (or already-consumed) block
    m, l, acc, k_last, v_last = jax.lax.fori_loop(
        0, n_steps - 1, step, (m0, l0, acc0, k, v))
    m, l, acc = attend(n_steps - 1, m, l, acc, k_last, v_last)
    return _finish_softmax(acc, l, q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, causal: bool = False,
                      window: Optional[int] = None) -> jax.Array:
    """Exact attention via all-to-all head<->sequence resharding.

    Inside `shard_map`: inputs are sequence-sharded [B, S/n, H, D]; an
    all-to-all regroups to head-sharded [B, S, H/n, D], blockwise local
    attention runs per head group (streaming softmax over S/n-sized key
    blocks, so peak score memory is O((H/n) * S * S/n) — the same
    H*(S/n)^2 per chip as ring, NOT the full [S, S]), and the inverse
    all-to-all restores sequence sharding. Requires H % n == 0.

    Unlike ring, a sliding `window` cannot skip key blocks here: every
    chip holds ALL query positions after the first all-to-all, so every
    key block intersects someone's window — the window is mask-only.
    """
    _check_window(causal, window)
    n = jax.lax.axis_size(axis_name)
    b, s_local, h, d = q.shape
    assert h % n == 0, "ulysses requires head count divisible by axis size"
    scale = 1.0 / (d ** 0.5)

    def to_heads(x):  # [B, S/n, H, D] -> [B, S, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def to_seq(x):    # [B, S, H/n, D] -> [B, S/n, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    kv = k.shape[2]
    if kv != h and kv % n:
        # GQA group count not divisible by the axis: pre-repeat K/V to
        # lcm(kv, n) — the SMALLEST head count the all-to-all can split
        # evenly (kv and n both divide h, so their lcm does too). The
        # remaining h/lcm repeat still happens locally per block, so only
        # lcm/kv x of GQA's bandwidth advantage is forfeited (the old
        # fallback repeated all the way to h).
        target = math.lcm(kv, n)
        if (kv, n) not in _WARNED_GQA_FALLBACK:
            _WARNED_GQA_FALLBACK.add((kv, n))
            logger.warning(
                "ulysses GQA fallback: kv_heads=%d not divisible by sp=%d; "
                "K/V pre-repeat to lcm=%d heads, so the all-to-all moves "
                "%dx the GQA-ideal K/V bytes. Use an sp degree dividing "
                "kv_heads to keep the full advantage.",
                kv, n, target, target // kv)
        k = jnp.repeat(k, target // kv, axis=2)
        v = jnp.repeat(v, target // kv, axis=2)
    # kv heads ride the all-to-all unrepeated (kv/n per chip); the block
    # update repeats them locally per key block
    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)

    s_total = s_local * n
    hq, kvh = h // n, kh.shape[2]
    m0 = jnp.full((b, hq, s_total), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hq, s_total), jnp.float32)
    acc0 = jnp.zeros((b, s_total, hq, d), jnp.float32)
    # key blocks of the local chunk size: [n, B, S/n, KV/n, D]
    kb = jnp.moveaxis(kh.reshape(b, n, s_local, kvh, d), 1, 0)
    vb = jnp.moveaxis(vh.reshape(b, n, s_local, kvh, d), 1, 0)
    offsets = jnp.arange(n) * s_local

    def blk(carry, xs):
        m, l, acc = carry
        k_blk, v_blk, k_off = xs
        m, l, acc = _block_attention(qh, k_blk, v_blk, m, l, acc, 0, k_off,
                                     causal, scale, window)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(blk, (m0, l0, acc0), (kb, vb, offsets))
    return to_seq(_finish_softmax(acc, l, q.dtype))


def resolve_sp_core(sp_kind: str, num_heads: Optional[int] = None,
                    n: Optional[int] = None,
                    window: Optional[int] = None):
    """THE dispatch point for the sequence-parallel attention core (shared
    by the SPMD pipeline, the decode prefill, and the standalone wrapper):
    'ring' streams K/V chunks via ppermute with a blockwise softmax
    (O((S/n)^2) score memory AND window-skipped ring steps — the
    long-context choice); 'ulysses' all-to-all reshards heads<->sequence
    with blockwise local attention (same per-chip score memory, cheaper
    collectives when heads >= chips). Validates the Ulysses
    head-divisibility requirement when `num_heads`/`n` are supplied
    (ulysses_attention also asserts it at trace time). A `window` binds
    the Mistral-style sliding-window mask into the returned core; callers
    keep the plain `core(q, k, v, axis, causal=True)` signature."""
    if sp_kind == "ring":
        core = ring_attention
    elif sp_kind == "ulysses":
        if num_heads is not None and n and num_heads % n:
            raise ValueError(f"ulysses sp={n} requires head count "
                             f"({num_heads}) divisible by sp")
        core = ulysses_attention
    else:
        raise ValueError(f"unknown sp_kind {sp_kind!r} (ring | ulysses)")
    if window is not None:
        core = partial(core, window=int(window))
    return core


def make_sequence_parallel_attention(mesh: Mesh, axis_name: str = "sp",
                                     kind: str = "ring",
                                     causal: bool = False,
                                     window: Optional[int] = None):
    """Build a jitted `fn(q, k, v) -> out` over globally-shaped [B, S, H, D]
    arrays with the sequence axis sharded over `axis_name`."""
    inner = resolve_sp_core(kind, window=window)
    spec = P(None, axis_name)

    @jax.jit
    def fn(q, k, v):
        return jax_compat.shard_map(
            partial(inner, axis_name=axis_name, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)

    def place(x):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return lambda q, k, v: fn(place(q), place(k), place(v))
