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
from ..ops import grouped_matmul as gm


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
# loop step multiplies one tile by its expert's matrices. How many
# rows a call's tiles have is `expert_tile`'s to say; this is its cap (256
# rows of a 2,048 x 768 expert are as many FLOPs in one bfloat16 pass as its
# weights are bytes on a v5e). What `expert_tile` returns also says which
# calls leave the loop for the grouped kernel (`GROUPED_RIDGE`, below).
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

# a call whose tile is at most this many rows walks its groups inside one
# grouped kernel (`ops/grouped_matmul.py`); above it the tile loop
# stays. Under the ridge a tile is bound by its expert's bytes and the loop
# pays a trip, three slices, a gather and a write for every touched expert,
# which the kernel does not (PERF.md, PR 41's table: one layer call alone,
# loop against grouped); above it a tile is bound by its three-pass
# products, which the loop's fusions run near the chip's peak. The cells'
# steps have tiles of 8 and 32, their spans 120 to 256
GROUPED_RIDGE = 64

# what `topk_ffn_delta` counts, in this order (a float32 vector; each is far
# below 2**24 a call). `rows_computed`: rows the products multiplied, a
# row's three parts once: a loop tile's rows, or every visit of one of the
# grouped kernel's row tiles to a group. `grouped_calls`: 1 where the call
# took the grouped kernel
MOE_STATS = ("assignments", "rows_computed", "experts_touched",
             "grouped_calls")


def _grouped_mode():
    """How this backend runs the grouped kernel: "mosaic" on a TPU, None
    where Mosaic cannot run (the tile loop serves every call); the tests
    put "interpret" here."""
    return "mosaic" if jax.default_backend() == "tpu" else None


def grouped_layout(tile: int):
    """(rows of the grouped kernel's row tile, whether every group starts
    on a row tile's first row) in a call whose groups fit a loop tile of
    `tile` rows (`expert_tile`).

    The row tile is that tile in whole bfloat16 sublane tiles of 16: a
    visit multiplies the whole row tile (three parts a row) by one block of
    the group's matrix, which takes the matrix unit about as long as the
    block's bytes take the HBM up to about 100 rows, and longer beyond
    (PERF.md, PR 41: lfm2's step, packed, at row tiles of 32 / 64 / 96 /
    128: 1.21 / 1.19 / 1.32 / 1.63 ms a call). Groups of a row or two (a
    loop tile of 8) lie packed as they sorted: a row tile then holds many
    groups, and starting each on a tile of its own would lay out sixteen
    rows for one (laguna's call 2.18 ms for 1.56). Groups near the row
    tile's size (a loop tile of 16 and more) each start on a row tile's
    first row: packed, half of them straddle two row tiles and are visited
    twice, and the second visit's products are not hidden behind bytes
    (lfm2's call 1.19 ms packed, 1.03 a group a tile)."""
    return -(-tile // 16) * 16, tile >= 16


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


# an expert that has no gate matrix is `down(act(up x))`, `act` the
# configuration's `expert_act`
ACTS = {"silu": jax.nn.silu, "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def expert_names(ex: Dict) -> tuple:
    """An expert's matrices in the order it multiplies them: a SwiGLU's
    three, or the two of an expert without a gate matrix."""
    return ("gate", "up", "down") if "gate" in ex else ("up", "down")


def _expert_ffn(x: jax.Array, w: Dict, act: str) -> jax.Array:
    """One expert's FFN of x [rows, D] over its `nn.Linear` matrices `w`
    [out, in] as stored -> float32 [rows, D']: `_swiglu` where it has a
    gate matrix, else `down(act(up x))`."""
    if "gate" in w:
        return _swiglu(x, w["gate"], w["up"], w["down"])
    hidden = ACTS[act](exact_dot(x, w["up"], w_contract=1)).astype(x.dtype)
    return exact_dot(hidden, w["down"], w_contract=1)


def topk_ffn_delta(params: Dict, normed: jax.Array, cfg: TransformerConfig,
                   held=None, layer=None, live=None):
    """Routed FFN delta of `normed` [B, S, D], and its counts.

    `params`: `router` {w [D, E][, bias]}, `experts` {gate, up [.., F, D],
    down [.., D, F]} (`nn.Linear` layout, no bias; a SwiGLU), or {up, down}
    alone (`down(act(up x))`, `act` = `cfg.expert_act`: what form an expert
    has is read from its leaves), the expert axis holding
    the `held` experts only, and where the model has one `shared` {[gate,]
    up [Fs, D], down [D, Fs]}, the expert every token goes through, which is
    added here (every chip of a deployment computes it alike: when shares
    are added up it counts once), times `sigmoid(shared_gate . token)`
    where the model has a `shared_gate` [1, D] beside it. Where the model
    has a `latent` {down [Dl, D], up [D, Dl]} the router still reads the
    token and the routed experts read and write its latent, `latent.down`
    of it, Dl wide (their matrices are [F, Dl] and [Dl, F]); their weighted
    sum is taken back up by `latent.up`, which is linear, so shares of the
    experts still add up. `held` = (first, count): the experts this
    caller computes, `first` possibly traced (an 'ep' device's slab); None
    = `cfg.held_experts`, or all. Assignments to other experts cost a sort
    key and nothing more, and add nothing here. `layer`, when given,
    indexes a leading layer axis of the expert leaves: the stacked blocks
    are then read one expert's matrices at a time and a whole layer's
    experts are never copied out of the stack. `live` (bool [B * S]; None =
    every token): the tokens that stand for something. The router drops
    nothing, so tokens do not compete and a row of padding changes no other
    row's result; but its assignments would read experts' weights and be
    counted, so they are handed on like another chip's: sorted last,
    multiplied by no expert, their delta zero (the served executor's dead
    slots, parallel/decode_rows.py).

    The assignments are sorted by expert, and the sorted groups are
    multiplied by their experts in one of two ways, by what the call's
    shapes say (`expert_tile(tokens, k, experts)`; no option):

    - a tile above `GROUPED_RIDGE` rows (a span of 4,096 tokens, top-8 of
      128: groups of 256, tiles of 256): each held expert's group is covered
      by whole tiles of that many rows, one loop step a tile
      (`_tile_loop`), bound by its three-pass products;
    - a tile at or under it (a step of 128 rows, top-4 of 32: groups of 16;
      a step of 32 rows, top-8 of 256: single assignments), on a backend
      that runs Mosaic: one kernel walks the groups, up, activation and
      down a visit, the hidden rows in VMEM (`_grouped`), bound by the
      touched experts' bytes.

    A row's result does not depend on the way, but for the order of the
    float32 sums. Returns (delta [B, S, D], stats float32
    [len(MOE_STATS)])."""
    b, s, d = normed.shape
    tokens = normed.reshape(-1, d)
    t, k = tokens.shape[0], cfg.num_experts_per_tok
    first, count = held or cfg.held_experts or (0, cfg.n_experts)
    experts, gates = topk_route(params["router"], tokens, cfg)
    rows_in, latent = tokens, params.get("latent")
    if latent:      # what the experts read: the token's latent
        rows_in = exact_dot(tokens, latent["down"],
                            w_contract=1).astype(tokens.dtype)
    local = experts.reshape(-1) - first                     # [A]
    mine = (local >= 0) & (local < count)
    if live is not None:
        mine &= jnp.repeat(live.reshape(-1), k)
    local = jnp.where(mine, local, count)                   # others sort last
    # the assignments sorted by expert (stable: by token within an expert;
    # other chips' last), by sorts and searches: a scatter of this many
    # single values is the slow way on the chip
    order = jnp.argsort(local, stable=True)
    tile = expert_tile(t, k, cfg.n_experts)
    mode = _grouped_mode() if tile <= GROUPED_RIDGE else None
    # where each held expert's group starts: the assignments before it. A
    # search a bound over a span's tens of thousands of sorted assignments;
    # a step's few hundred are counted in one pass (`gm.pick`'s reason)
    held = jnp.arange(count + 1)
    bounds = jnp.sum(local[None, :] < held[:, None], axis=1) if mode \
        else jnp.searchsorted(local[order], held)
    # each token's assignments: where they sorted to, and their gates
    sorted_at = jnp.argsort(order).reshape(t, k)
    gates = jnp.where(mine, gates.reshape(-1), 0.0).reshape(t, k)
    ways = (rows_in, params["experts"], layer, order, bounds, sorted_at,
            gates)
    if mode:
        delta, rows = _grouped(*ways, local.reshape(t, k),
                               *grouped_layout(tile), mode == "interpret",
                               cfg.expert_act)
    else:
        delta, rows = _tile_loop(*ways, tile, cfg.n_experts, cfg.expert_act)
    if latent:
        delta = exact_dot(delta.astype(tokens.dtype), latent["up"],
                          w_contract=1)
    if "shared" in params:
        shared = _expert_ffn(tokens, params["shared"], cfg.expert_act)
        if "shared_gate" in params:
            shared = shared * jax.nn.sigmoid(exact_dot(
                tokens, params["shared_gate"], w_contract=1))
        delta = delta + shared
    sizes = bounds[1:] - bounds[:-1]
    stats = jnp.stack([jnp.sum(mine), rows, jnp.sum(sizes > 0),
                       1 if mode else 0]).astype(jnp.float32)
    return delta.reshape(b, s, d).astype(normed.dtype), stats


def _back_to_tokens(delta, out, sorted_at, gates, base, end):
    """`delta` [T, D] plus, for each token, its gates times its assignments'
    rows of `out`, which holds the sorted assignments `[base, end)` from its
    row 0. Rows are selected, never multiplied by a zero gate: a row no
    expert here wrote may hold anything."""
    for slot in range(sorted_at.shape[1]):
        at = sorted_at[:, slot]
        here = (at >= base) & (at < end)
        rows = jnp.take(out, jnp.clip(at - base, 0, out.shape[0] - 1),
                        axis=0)
        delta = delta + jnp.where(here[:, None],
                                  gates[:, slot][:, None] * rows, 0.0)
    return delta


def _grouped(tokens, ex, layer, order, bounds, sorted_at, gates, local,
             row_tile: int, aligned: bool, interpret: bool,
             act: str = "silu"):
    """The sorted groups through their experts in one grouped kernel
    (`ops/grouped_matmul.py::grouped_ffn`): `down(silu(gate x) * up x)`, or
    `down(act(up x))` of experts without a gate matrix; the rows go in as
    they were gathered and the hidden stays in the kernel; the arithmetic is
    `_expert_ffn`'s over `exact_dot`. -> (delta [T, D] float32, rows the
    kernel multiplied).

    The stack is handed over as it lies, its leading axes flattened (a free
    reshape), and group g is its matrix `layer * experts + g`: a layer's
    experts are never taken out of it. `local` [T, k]: each assignment's
    group, `count` for other chips'. `aligned`: every group starts on a row
    tile's first row, so a group that fits a tile is one visit; else the
    groups lie packed as they sorted (`grouped_layout`)."""
    (t, d), k = tokens.shape, sorted_at.shape[1]
    count = bounds.shape[0] - 1
    stacks = {name: w.reshape((-1,) + w.shape[-2:]) for name, w in ex.items()}
    first_group = 0 if layer is None else layer * ex["up"].shape[-3]
    n_assign = t * k
    sizes = bounds[1:] - bounds[:-1]
    sorted_tokens = order // k
    if aligned:
        padded = -(-sizes // row_tile) * row_tile
        starts = jnp.cumsum(padded) - padded
        n_tiles = -(-n_assign // row_tile) + min(count, n_assign)
        # a group's rows lie `shift` rows after where they sorted to; row r
        # of the layout holds the sorted assignment `r - shift` of the last
        # group that starts at or before it (a row between two groups takes
        # some assignment's token: nobody owns it). Short tables are read
        # by comparison, never by a gather of single values (`gm.pick`)
        shift = starts - bounds[:-1]
        row = jnp.arange(n_tiles * row_tile)
        at = jnp.clip(row - gm.pick(shift, gm.count_up_to(starts, row) - 1),
                      0, n_assign - 1)
        token_of_row = gm.pick(sorted_tokens, at)
        laid_at = sorted_at + gm.pick(shift, jnp.minimum(local, count - 1))
    else:
        starts = bounds[:-1]
        n_tiles = -(-n_assign // row_tile)
        token_of_row = jnp.concatenate([sorted_tokens, jnp.zeros(
            (n_tiles * row_tile - n_assign,), order.dtype)])
        laid_at = sorted_at
    n_rows = n_tiles * row_tile
    items = gm.group_items(starts, starts + sizes, first_group, row_tile,
                           gm.max_items(n_rows, count, row_tile))
    out = gm.grouped_ffn(
        jnp.take(tokens, token_of_row, axis=0),
        [stacks[name] for name in expert_names(ex)], items,
        row_tile=row_tile, act=ACTS[act], interpret=interpret)
    # other chips' assignments lie nowhere in the layout
    delta = _back_to_tokens(jnp.zeros((t, d), jnp.float32), out,
                            jnp.where(local < count, laid_at, n_rows),
                            gates, 0, n_rows)
    visits = jnp.where(bounds[count] > 0, items.count, 0)
    return delta, visits * row_tile


def _tile_loop(tokens, ex, layer, order, bounds, sorted_at, gates,
               tile: int, n_experts: int, act: str = "silu"):
    """The sorted groups times their experts a tile of `tile` rows a loop
    step. -> (delta [T, D] float32, rows the tiles multiplied)."""
    (t, d), k = tokens.shape, sorted_at.shape[1]
    count = bounds.shape[0] - 1
    n_assign = t * k
    # an expert is given a token at most once, so a group has at most
    # ceil(t / tile) tiles; all groups together have at most one tile a
    # group more than the assignments fill, and no more than assignments
    n_tiles = min(count * -(-t // tile), -(-n_assign // tile) + count,
                  n_assign)
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
                                 // (n_experts * tile)) + count)
    kept_rows = min(round_tiles * tile, n_assign + tile)
    # a group's last tile may run past the last assignment
    order = jnp.concatenate([order, jnp.full((tile,), n_assign, order.dtype)])

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
            y = _expert_ffn(x, {name: matrix(name, e)
                                for name in expert_names(ex)}, act)
            return jax.lax.dynamic_update_slice_in_dim(
                out, y, tile_start[i] - base, 0)

        out = jax.lax.fori_loop(first_tile, last_tile, one_tile,
                                jnp.zeros((kept_rows, d), jnp.float32))
        return _back_to_tokens(delta, out, sorted_at, gates, base, end)

    delta = jax.lax.fori_loop(0, -(-used // round_tiles), one_round,
                              jnp.zeros((t, d), jnp.float32))
    return delta, used * tile


def ep_topk_ffn_delta(params: Dict, normed: jax.Array,
                      cfg: TransformerConfig, axis: str):
    """`topk_ffn_delta` under `shard_map` with the expert axis of
    `params["experts"]` sharded over `axis`: each device computes its
    slab's part and one psum adds them."""
    count = cfg.n_experts // jax.lax.axis_size(axis)
    first = jax.lax.axis_index(axis) * count
    delta, stats = topk_ffn_delta(params, normed, cfg, held=(first, count))
    return jax.lax.psum(delta, axis), jax.lax.psum(stats, axis)
