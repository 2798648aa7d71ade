"""Expert parallelism: a switch-routed FFN with experts sharded over an
'ep' mesh axis.

NEW capability beyond the reference (SURVEY.md §2.4: PipeEdge has no MoE
models, so expert parallelism is n/a there). This module provides the
mesh-axis mechanics so an MoE block composes with the pipeline the same way
tp/sp do: parameters shard over 'ep' (each device owns n_experts/n local
experts), tokens are routed top-1 with a fixed per-expert capacity (static
shapes — XLA requirement), each device computes only its own experts'
tokens, and one `psum` combines the expert outputs.

Routing semantics (Switch Transformer style, top-1):
- router logits [T, E] -> softmax -> each token's expert + gate weight;
- per expert, the C highest-probability tokens assigned to it are kept
  (C = capacity_factor * T / E, rounded up); overflow tokens pass through
  unchanged (the standard capacity-drop residual behavior).
- T is the token set the caller presents: under data parallelism each dp
  shard routes its own tokens with its own capacity (the standard
  data-parallel MoE semantics) — outputs are batch-size-dependent by
  construction, like any capacity-routed MoE.

The GPT-2 family consumes this as `moe_ffn_delta` for its routed-FFN
blocks (models/gpt2.py, registry models pipeedge/gpt2-moe-8e and
pipeedge/test-tiny-moe), so MoE decoders run through the shard engine,
host/SPMD pipelines, and KV-cache decoding (tests/test_moe_family.py).

Exactness: `ep_ffn` over an n-device 'ep' axis matches the single-device
reference (`reference_moe_ffn`) to float tolerance (the distributed
combine re-associates one add) — tested in tests/test_expert.py.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import jax_compat
from ..models.layers import TransformerConfig, exact_dot, gelu


def init_moe_params(cfg: TransformerConfig, n_experts: int,
                    seed: int = 0) -> Dict:
    """Router + per-expert MLP params (expert axis leading)."""
    rng = np.random.default_rng(seed)
    d, f = cfg.hidden_size, cfg.intermediate_size

    def glorot(*shape):
        fan = shape[-2] + shape[-1]
        return jnp.asarray(rng.normal(0, math.sqrt(2.0 / fan), shape),
                           jnp.float32)

    return {
        "router": {"w": glorot(d, n_experts),
                   "b": jnp.zeros((n_experts,), jnp.float32)},
        "experts": {
            "mlp_up": {"w": glorot(n_experts, d, f),
                       "b": jnp.zeros((n_experts, f), jnp.float32)},
            "mlp_down": {"w": glorot(n_experts, f, d),
                         "b": jnp.zeros((n_experts, d), jnp.float32)},
        },
    }


def _routing(router, x, n_experts: int, capacity: int):
    """Top-1 routing with per-expert capacity.

    Returns (expert_of_token [T], gate [T], keep [E, C] token indices,
    kept [E, C] validity) — deterministic, static shapes."""
    t = x.shape[0]
    logits = x @ router["w"] + router["b"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate = jnp.max(probs, axis=-1)            # [T]
    expert = jnp.argmax(probs, axis=-1)       # [T]
    # per expert: the C highest-gate tokens assigned to it
    assigned = jnp.where(expert[None, :] == jnp.arange(n_experts)[:, None],
                         gate[None, :], -jnp.inf)          # [E, T]
    top_gate, keep = jax.lax.top_k(assigned, capacity)     # [E, C]
    kept = jnp.isfinite(top_gate)
    return expert, gate, keep, kept


def moe_capacity(n_tokens: int, n_experts: int,
                 capacity_factor: float) -> int:
    """Per-expert token capacity (static; standard switch formula)."""
    return max(1, min(n_tokens,
                      math.ceil(capacity_factor * n_tokens / n_experts)))


def _scatter_expert_deltas(experts: Dict, tokens: jax.Array, gate, keep,
                           kept, act) -> jax.Array:
    """THE expert-compute core shared by the single-device delta FFN and
    the ep-sharded body: vmap act(x@up)@down over the (possibly local)
    expert slab, gate, zero invalid slots, scatter-add into token rows.
    One implementation, so the family FFN and the 'ep' axis cannot
    diverge."""
    def one_expert(w_up, b_up, w_down, b_down, ids, valid):
        xe = tokens[ids]
        up = act(xe @ w_up + b_up)
        ye = up @ w_down + b_down
        return jnp.where(valid[:, None], ye * gate[ids][:, None], 0.0), ids

    deltas, ids = jax.vmap(one_expert)(
        experts["mlp_up"]["w"], experts["mlp_up"]["b"],
        experts["mlp_down"]["w"], experts["mlp_down"]["b"], keep, kept)
    return jnp.zeros_like(tokens).at[ids.reshape(-1)].add(
        deltas.reshape(-1, tokens.shape[-1]))


def moe_ffn_delta(params: Dict, normed: jax.Array, n_experts: int,
                  capacity_factor: float, *, act) -> jax.Array:
    """Single-device switch-FFN **delta**: gate * expert(normed) per kept
    token, zeros for capacity-dropped tokens. Pre-LN families add this to
    the raw residual (h = x + delta), so the residual semantics live with
    the caller — this is the form the GPT-2 MoE blocks use
    (models/gpt2.py). `act` is required (GPT-2 uses gelu_new; a defaulted
    activation would be a silent-wrong-numbers trap)."""
    b, s, d = normed.shape
    tokens = normed.reshape(-1, d)
    capacity = moe_capacity(tokens.shape[0], n_experts, capacity_factor)
    _, gate, keep, kept = _routing(params["router"], tokens, n_experts,
                                   capacity)
    delta = _scatter_expert_deltas(params["experts"], tokens, gate, keep,
                                   kept, act)
    return delta.reshape(b, s, d).astype(normed.dtype)


def reference_moe_ffn(params: Dict, x: jax.Array, n_experts: int,
                      capacity_factor: float = 1.25) -> jax.Array:
    """Single-device oracle: identical routing, experts applied in a loop."""
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    capacity = moe_capacity(tokens.shape[0], n_experts, capacity_factor)
    _, gate, keep, kept = _routing(params["router"], tokens, n_experts,
                                   capacity)
    out = tokens  # capacity-dropped tokens pass through (residual)
    for e in range(n_experts):
        ids = keep[e]
        xe = tokens[ids]
        up = gelu(xe @ params["experts"]["mlp_up"]["w"][e]
                  + params["experts"]["mlp_up"]["b"][e])
        ye = up @ params["experts"]["mlp_down"]["w"][e] \
            + params["experts"]["mlp_down"]["b"][e]
        ye = ye * gate[ids][:, None] + tokens[ids]
        out = out.at[ids].set(jnp.where(kept[e][:, None], ye, out[ids]))
    return out.reshape(b, s, d)


def _ep_local(params: Dict, x: jax.Array, *, n_experts: int,
              capacity: int, axis: str, act=gelu) -> jax.Array:
    """Per-device body under shard_map: local experts [E/n, ...], tokens
    replicated; each device computes its experts' capacity slots and a psum
    combines."""
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    _, gate, keep, kept = _routing(params["router"], tokens, n_experts,
                                   capacity)
    combined = _ep_delta_from_routing(params, tokens, gate, keep, kept,
                                      n_experts, axis, act)
    return (tokens + combined).reshape(b, s, d)


def _ep_delta_from_routing(params: Dict, tokens: jax.Array, gate, keep,
                           kept, n_experts: int, axis: str,
                           act) -> jax.Array:
    """This device's expert rows of the global routing tables -> local
    deltas (shared core) -> psum combine across `axis`. Used by the
    standalone ep FFN and the expert-parallel decode step."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    e_local = n_experts // n
    first = idx * e_local
    my_keep = jax.lax.dynamic_slice_in_dim(keep, first, e_local, axis=0)
    my_kept = jax.lax.dynamic_slice_in_dim(kept, first, e_local, axis=0)
    local = _scatter_expert_deltas(params["experts"], tokens, gate, my_keep,
                                   my_kept, act)
    return jax.lax.psum(local, axis)


def ep_ffn_delta(params: Dict, normed: jax.Array, n_experts: int,
                 capacity_factor: float, axis: str, *, act) -> jax.Array:
    """Expert-parallel counterpart of `moe_ffn_delta`: the same routed-FFN
    delta with the expert slab sharded over `axis` (call under shard_map).
    Exact vs the single-device delta — top-1 routing means the psum adds
    exactly one nonzero term per token."""
    b, s, d = normed.shape
    tokens = normed.reshape(-1, d)
    capacity = moe_capacity(tokens.shape[0], n_experts, capacity_factor)
    _, gate, keep, kept = _routing(params["router"], tokens, n_experts,
                                   capacity)
    delta = _ep_delta_from_routing(params, tokens, gate, keep, kept,
                                   n_experts, axis, act)
    return delta.reshape(b, s, d).astype(normed.dtype)


def make_ep_ffn_fn(cfg: TransformerConfig, mesh: Mesh, n_experts: int,
                   capacity_factor: float = 1.25, axis: str = "ep", *,
                   act):
    """Jitted `fn(params, x) -> x`: switch-FFN with experts sharded over
    `axis`. Place params with `shard_moe_params` first. Token count must be
    static per call (standard XLA); capacity derives from it."""
    n = mesh.shape[axis]
    if n_experts % n:
        raise ValueError(f"n_experts ({n_experts}) must divide by the ep "
                         f"axis size ({n})")

    param_specs = {
        "router": {"w": P(), "b": P()},
        "experts": {
            "mlp_up": {"w": P(axis), "b": P(axis)},
            "mlp_down": {"w": P(axis), "b": P(axis)},
        },
    }

    def fn(params, x):
        b, s, _ = x.shape
        capacity = moe_capacity(b * s, n_experts, capacity_factor)
        body = jax_compat.shard_map(
            partial(_ep_local, n_experts=n_experts, capacity=capacity,
                    axis=axis, act=act),
            mesh=mesh, in_specs=(param_specs, P()), out_specs=P())
        return body(params, x)

    return jax.jit(fn)


def shard_moe_params(params: Dict, mesh: Mesh, axis: str = "ep") -> Dict:
    """Place MoE params: experts sharded over `axis`, router replicated."""
    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return {
        "router": {k: put(v, P()) for k, v in params["router"].items()},
        "experts": jax.tree_util.tree_map(
            lambda v: put(v, P(axis)), params["experts"]),
    }


# -- top-k routing without drops (the keye and kimi families' expert layer)
#
# A token goes to its `num_experts_per_tok` best experts, every assignment
# is computed, and the layer is told which experts it holds: it routes over
# all of them and computes the part of the result that its own give. On one
# chip that holds all nothing is exchanged; a chip that holds a share (one
# of the chips a deployment divides each layer over) leaves out what the
# absent experts would add; under an 'ep' axis each device passes its slab
# and one psum adds the parts (`_ep_delta_from_routing`'s shape for top-1).

# rows of one tile of the grouped product, at most: the assignments are
# sorted by expert, each expert's group is covered by whole tiles, and one
# loop step multiplies one tile by its expert's three matrices. How many
# rows a call's tiles have is `expert_tile`'s to say; this is its cap (256
# rows of a 2,048 x 768 expert are as many FLOPs in one bfloat16 pass as its
# weights are bytes on a v5e).
EXPERT_TILE = 256

# standard deviations of a group's size that a tile leaves room for above
# the mean, so that nearly every group is one tile
GROUP_SPREAD = 3


def expert_tile(tokens: int, per_tok: int, n_experts: int) -> int:
    """Rows of one tile in a call whose `tokens` tokens go to `per_tok` of
    `n_experts` experts each: the smallest multiple of 8 that holds what an
    expert is given with room for its spread, at most `EXPERT_TILE`.

    An expert's group is a binomial where the router is even: each token
    picks it with probability `per_tok / n_experts` (whatever share of the
    experts the caller holds), so it has `tokens * per_tok / n_experts`
    assignments on average, `GROUP_SPREAD` deviations more at most, and
    never more than the tokens.

    The cost model (one tile of the loop timed on a v5e at 8 to 256 rows,
    float32 rows over four bfloat16 expert shapes; PERF.md, PR 38): a tile
    costs 14 to 27 us whatever it holds (its rows gathered, its matrices
    sliced, its result written, the expert's bytes read below the chip's
    peak rate), then the larger of its expert's bytes and its rows'
    three-pass products, which meet near 64 rows. So a group's second tile
    always costs more than the same rows in its first, and rows a tile has
    beyond its group are products or, under 64 rows, nearly free: the
    smallest tile that nearly every group fits whole is the cheapest, at
    every load up to the cap. Groups above the cap take tiles of the cap:
    the fixed cost of more tiles outweighs the half tile of padding a group
    that a smaller tile would save (96 rows lost to 256 in every prefill
    that has such groups, and a 96-row product costs 15 to 58% more a row
    than a 256-row one)."""
    share = per_tok / n_experts
    mean = tokens * share
    group = min(tokens, mean + GROUP_SPREAD * math.sqrt(mean * (1 - share)))
    return min(EXPERT_TILE, -(-math.ceil(group) // 8) * 8)


# tiles whose results are kept at a time, as a multiple of what the held
# experts are given when the router spreads its choices evenly
ROUND_SLACK = 4

# what `topk_ffn_delta` counts, in this order (a float32 vector; each is far
# below 2**24 a call)
MOE_STATS = ("assignments", "rows_computed", "experts_touched")


def topk_route(router: Dict, tokens: jax.Array, cfg: TransformerConfig):
    """(experts [T, k], gates [T, k]) of `tokens` [T, D], in float32 over
    all the experts the router `{w [D, E][, bias [E]]}` knows. Ties go to
    the lower expert (`lax.top_k`).

    `cfg.router` "softmax": the top-k of the softmax, gates renormalised
    over the kept where the config says so. "sigmoid": each expert's score
    is its own sigmoid; the k are chosen on score + `bias` (a correction
    that steers the choice and is no part of the gate), and the gates are
    the chosen scores, renormalised where the config says so (over their
    sum + `cfg.gate_sum_eps`, the constant the model's own code guards the
    division with), times `cfg.routed_scaling_factor`."""
    logits = jnp.dot(tokens.astype(jnp.float32),
                     router["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    k = cfg.num_experts_per_tok
    if cfg.router == "softmax":
        gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    elif cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(
            scores + router["bias"].astype(jnp.float32), k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        raise ValueError(f"no router {cfg.router!r}")
    if cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                         + cfg.gate_sum_eps)
    return experts, gates * cfg.routed_scaling_factor


def _swiglu(x: jax.Array, gate_w, up_w, down_w) -> jax.Array:
    """down(silu(gate x) * up x) of x [rows, D] over `nn.Linear` matrices
    [out, in] as stored -> float32 [rows, D]."""
    def product(a, w):
        return exact_dot(a, w, w_contract=1)

    hidden = (jax.nn.silu(product(x, gate_w))
              * product(x, up_w)).astype(x.dtype)
    return product(hidden, down_w)


def topk_ffn_delta(params: Dict, normed: jax.Array, cfg: TransformerConfig,
                   held=None, layer=None):
    """Routed SwiGLU FFN delta of `normed` [B, S, D], and its counts.

    `params`: `router` {w [D, E][, bias]}, `experts` {gate, up [.., F, D],
    down [.., D, F]} (`nn.Linear` layout, no bias), the expert axis holding
    the `held` experts only, and where the model has one `shared` {gate, up
    [Fs, D], down [D, Fs]}, the expert every token goes through, which is
    added here (every chip of a deployment computes it alike: when shares
    are added up it counts once), times `sigmoid(shared_gate . token)`
    where the model has a `shared_gate` [1, D] beside it. `held` = (first, count): the experts this
    caller computes, `first` possibly traced (an 'ep' device's slab); None
    = `cfg.held_experts`, or all. Assignments to other experts cost a sort
    key and nothing more, and add nothing here. `layer`, when given,
    indexes a leading layer axis of the expert leaves: the stacked blocks
    are then sliced one tile's matrices at a time and a whole layer's
    experts are never copied out of the stack.

    The assignments are sorted by expert and each held expert's group is
    covered by whole tiles of `expert_tile(tokens, k, experts)` rows, one
    loop step a tile. The tile follows what an expert is given in this call
    (a step of 128 rows, top-4 of 32: groups of 16, tiles of 32; a span of
    4,096 tokens, top-8 of 128: groups of 256, tiles of 256); a row's
    result does not depend on it, only how many rows of padding are
    multiplied beside that row.

    Returns (delta [B, S, D], stats float32 [len(MOE_STATS)])."""
    b, s, d = normed.shape
    tokens = normed.reshape(-1, d)
    t, k = tokens.shape[0], cfg.num_experts_per_tok
    first, count = held or cfg.held_experts or (0, cfg.n_experts)
    experts, gates = topk_route(params["router"], tokens, cfg)
    local = experts.reshape(-1) - first                     # [A]
    mine = (local >= 0) & (local < count)
    local = jnp.where(mine, local, count)                   # others sort last
    n_assign = t * k

    tile = expert_tile(t, k, cfg.n_experts)
    # an expert is given a token at most once, so a group has at most
    # ceil(t / tile) tiles; all groups together have at most one tile a
    # group more than the assignments fill, and no more than assignments
    n_tiles = min(count * -(-t // tile), -(-n_assign // tile) + count,
                  n_assign)
    # the assignments sorted by expert (stable: by token within an expert;
    # other chips' last), by sorts and searches: a scatter of this many
    # single values is the slow way on the chip
    order = jnp.argsort(local, stable=True)
    bounds = jnp.searchsorted(local[order], jnp.arange(count + 1))
    group_first, sizes = bounds[:-1], bounds[1:] - bounds[:-1]
    tiles_of = -(-sizes // tile)
    tile_ends = jnp.cumsum(tiles_of)
    used = tile_ends[-1]
    # tile i: its expert, and the sorted assignment it starts at. A group's
    # last tile runs past the group's end into the rows of later groups;
    # their own tiles come later in the loop and write those rows again, so
    # no group is padded and no row is moved to make room
    tile_id = jnp.arange(n_tiles)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_ends, tile_id, side="right"), count - 1)
    tile_start = group_first[tile_expert] + tile * (
        tile_id - (tile_ends - tiles_of)[tile_expert])
    # the tiles' results are kept for one round of tiles at a time
    # (`ROUND_SLACK`; every tile where all experts are held). One round is
    # all there is unless the routing is that skewed, and nothing of the
    # size assignments x hidden is built for a share
    round_tiles = min(n_tiles, -(-ROUND_SLACK * n_assign * count
                                 // (cfg.n_experts * tile)) + count)
    kept_rows = min(round_tiles * tile, n_assign + tile)
    # each token's assignments: where they sorted to, and their gates
    sorted_at = jnp.argsort(order).reshape(t, k)
    gates = jnp.where(mine, gates.reshape(-1), 0.0).reshape(t, k)
    # a group's last tile may run past the last assignment
    order = jnp.concatenate([order, jnp.full((tile,), n_assign, order.dtype)])

    ex = params["experts"]

    def matrix(name, e):
        w = ex[name]
        if layer is None:
            return jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
        return jax.lax.dynamic_slice(
            w, (layer, e, 0, 0), (1, 1) + w.shape[2:])[0, 0]

    def one_round(r, delta):
        first_tile = r * round_tiles
        last_tile = jnp.minimum(first_tile + round_tiles, used)
        base = tile_start[first_tile]
        end = jnp.where(last_tile < used,
                        tile_start[jnp.minimum(last_tile, n_tiles - 1)],
                        bounds[count])

        def one_tile(i, out):
            # the tile's rows, gathered here from the tokens
            at = jax.lax.dynamic_slice_in_dim(order, tile_start[i], tile)
            x = jnp.take(tokens, jnp.minimum(at // k, t - 1), axis=0)
            e = tile_expert[i]
            y = _swiglu(x, *(matrix(name, e)
                             for name in ("gate", "up", "down")))
            return jax.lax.dynamic_update_slice_in_dim(
                out, y, tile_start[i] - base, 0)

        out = jax.lax.fori_loop(first_tile, last_tile, one_tile,
                                jnp.zeros((kept_rows, d), jnp.float32))
        # back to the tokens: each of a token's k assignments in turn
        for slot in range(k):
            at = sorted_at[:, slot]
            here = (at >= base) & (at < end)
            rows = jnp.take(out, jnp.clip(at - base, 0, kept_rows - 1),
                            axis=0)
            delta = delta + jnp.where(here, gates[:, slot],
                                      0.0)[:, None] * rows
        return delta

    delta = jax.lax.fori_loop(0, -(-used // round_tiles), one_round,
                              jnp.zeros((t, d), jnp.float32))
    if "shared" in params:
        shared = _swiglu(tokens, *(params["shared"][name]
                                   for name in ("gate", "up", "down")))
        if "shared_gate" in params:
            shared = shared * jax.nn.sigmoid(exact_dot(
                tokens, params["shared_gate"], w_contract=1))
        delta = delta + shared
    stats = jnp.stack([jnp.sum(mine), used * tile,
                       jnp.sum(sizes > 0)]).astype(jnp.float32)
    return delta.reshape(b, s, d).astype(normed.dtype), stats


def ep_topk_ffn_delta(params: Dict, normed: jax.Array,
                      cfg: TransformerConfig, axis: str):
    """`topk_ffn_delta` under `shard_map` with the expert axis of
    `params["experts"]` sharded over `axis`: each device computes its
    slab's part and one psum adds them."""
    count = cfg.n_experts // jax.lax.axis_size(axis)
    first = jax.lax.axis_index(axis) * count
    delta, stats = topk_ffn_delta(params, normed, cfg, held=(first, count))
    return jax.lax.psum(delta, axis), jax.lax.psum(stats, axis)
