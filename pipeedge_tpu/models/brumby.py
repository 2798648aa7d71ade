"""Brumby (`model_type` brumby; Manifest AI's Brumby-14B-Base): Qwen3's dense
trunk with every attention replaced by POWER RETENTION, a linear attention
whose keys are expanded to their symmetric second power and whose memory is
gated by data, a KV head a gate. All blocks are alike, and none keeps a key
or a value: the family's cache has no position axis at all.

The block, `x` [B, S, D], plain RMSNorm (`rms_norm_eps` 1e-6), no bias:
  h  = x + Mixer(rms(x; input_layernorm))
  x' = h + down(silu(gate u) * up u),  u = rms(h; post_attention_layernorm)
  logits = rms(h_last; norm) @ lm_head^T        (two tables, untied)

**Power retention**, `H` query heads and `G` KV heads of `hd` (`R = H / G`
query heads read one KV head): `q_t^h`, `k_t^g`, `v_t^g` from `q_proj`,
`k_proj`, `v_proj`; q and k RMS-normed a head (`q_norm`, `k_norm`) and
rotated over the whole head (halves layout, `rope_theta`, absolute
position); a gate a KV head, `a_t^g = log sigmoid(g_proj(u_t))` (`g_proj`
`D -> G`, float32). With `q' = q / hd^(1/4)`, `k' = k / hd^(1/4)`, so that
`q' . k' = q . k / sqrt(hd)`:
  attention form (what the benchmark's plain reference computes):
    A_t = sum_(i<=t) a_i;  w_tj = exp(A_t - A_j) (q'_t . k'_j)^2,  j <= t
    y_t^h = sum_j w_tj v_j / (sum_j w_tj + eps)
  recurrent form (`retention_step`, what a decode step runs), a KV head's
  state `S` [hd, F] and sum of keys `z` [F]:
    S_t = e^(a_t) S_(t-1) + v_t phi(k'_t)^T;  z_t = e^(a_t) z_(t-1) + phi(k'_t)
    y_t^h = S_t phi(q'_t^h) / (z_t . phi(q'_t^h) + eps)
  chunked form (`retention_chunked`, what a span runs, chunks of `C` =
  `cfg.linear_chunk`, `l_i` the sum of `a` up to `i` inside the chunk):
    num_i = sum_(j<=i) exp(l_i - l_j) (q'_i . k'_j)^2 v_j + e^(l_i) S_prev phi(q'_i)
    den_i = sum_(j<=i) exp(l_i - l_j) (q'_i . k'_j)^2     + e^(l_i) z_prev . phi(q'_i)
    S_new = e^(l_C) S_prev + sum_j e^(l_C - l_j) v_j phi(k'_j)^T   (likewise z)
  with no inverse. The degree is 2: every weight is >= 0 and there is no
  softmax. The heads' `y` joined, `o_proj`; no output gate and no output norm.

**`phi`** is the symmetric second power of an `hd`-vector without its
duplicates, laid out for the chip: BY DIAGONALS, entry `d hd + a` = `c_d x_a
x_((a - d) mod hd)` for `d` = 0 .. `hd / 2`, `c_0` = 1 (the squares), `c_d`
= sqrt(2) for `0 < d < hd / 2` (each pair at distance `d` round the circle
once) and `c_(hd/2)` = 1 (each antipodal pair twice), so that `phi(q) .
phi(k) = (q . k)^2` exactly as the `hd (hd + 1) / 2` = 8,256 distinct
products give it. `F = (hd / 2 + 1) hd` = **8,320** at `hd` 128: 65 whole
rows of 128 lanes, the 64.5 that 8,256 is rounded up by the half row the
antipodal diagonal holds twice (0.8% more state than the least; the full
square would be 16,384). Why diagonals: a diagonal of `phi(x)` is `x` times
`x` rolled `d` lanes, so a kernel expands `q'` and `k'` in VMEM from rows of
128 lanes with one lane rotation a row (`ops/retention_step.py`) and jnp
with one `roll`; a triangle's rows have 128 different lengths. The layout is
the program's own: the reference never forms `phi`.

**Cache** (`cache_leaves`): `pr_state` `[L, B, G, hd, F]` (the lanes are
`phi`'s, the sublanes the value's: 34.1 MB a request a layer at the
published sizes, whatever the context) and `pr_sum` `[L, B, G, F]`, a row a
REQUEST, read and replaced whole by every call; `stats`. **No leaf is a row
a position**: a stage binds ONE attend width (`parallel/decode.py::
_read_len`), so a generation builds one span program and one step program,
and `max_len` bounds the rotary positions and nothing in memory.

**What a call reads.** A step reads its layer's state once and writes it
once where the decode driver places the leaf (`decode.WHOLE_IN_PLACE_BYTES`)
and Mosaic runs: `ops/retention_step.py`, the stack aliased in and out
(`state_kernel_mode`, read off the call); elsewhere `retention_step` hands
the rows' state back for the driver to write. A span reads the state once
and writes it once, a cell at a time (`retention_chunked` walks cells of a
KV head of a few rows, `CELL_BYTES`: a chunk's `phi(Q)` of ALL heads is 1.36
GB at 8 rows, of a cell's five heads of two rows 43 MB).

**Precision.** Weights as stored (bfloat16; `g_proj` float32); activations,
state and sums float32: products with weights through `exact_dot`, `q . k`,
the state's products at `HIGHEST`, a step's state update float32
multiplications and sums on the vector unit, in the kernel as in the jnp
step. The decays are data and compound over a prompt: `l` is a triangular
product at `HIGHEST`, every decay the `exp` of a difference of sums, and the
two that compound (a step's `e^a`, a chunk's `e^(l_C)`) are
`decoder.exp_ulp`'s (PERF.md row 29). The model has no discrete choice.

**Prefill** runs in spans of `cfg.prefill_chunk` positions through the
decode-shaped stage program; a span's last chunk may be short (padded with
`a` = 0 and `k'` = 0, which leave the state).

Refused by name: the forward path (`sublayer`), tp, sp and ep meshes, the
int8 cache, `--kv-pages` (a page holds positions; this cache has none), the
SPMD wave decoder and speculative verify (a rejected draft would need the
state of an earlier position).

Weight format (`model.layers.N.`): `self_attn.{q,k,v,o,g}_proj.weight`,
`self_attn.{q,k}_norm.weight`, `mlp.{gate,up,down}_proj.weight`,
`input_layernorm`, `post_attention_layernorm`; `model.norm`,
`model.embed_tokens`, `lm_head` (untied).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..ops import retention_step as retention_kernel
from . import ShardConfig, decoder
from .decoder import exp_ulp, in_row_chunks, lin
from .layers import (TransformerConfig, rms_norm, rope_frequencies,
                     rotate_halves)
from .shard import CacheLeaf, FamilySpec

# what a block step counts into the cache's `stats` leaf, in this order:
# positions by form, and the stepped positions whose state the in-place
# kernel updated (`state_kernel_mode`)
STATS = ("retention_positions_chunked", "retention_positions_stepped",
         "retention_steps_in_place")

# activations, state and sums (module docstring, Precision)
ACTIVATIONS = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST

# what the sum of the weights is guarded with (the configuration's `assumed`)
EPS = 1e-6


def prefill_span(cfg: TransformerConfig) -> int:
    return cfg.prefill_chunk


def expanded(head_dim: int) -> int:
    """`F`, the lanes of `phi` of a head (module docstring)."""
    return retention_kernel.diagonals(head_dim) * head_dim


def cache_leaves(cfg: TransformerConfig) -> Dict:
    """The cache's leaves (module docstring, Cache): what follows `[L, B]`
    in the state and the sum of keys, every block's."""
    groups, hd = cfg.kv_heads, cfg.head_dim
    if hd % 2:
        raise ValueError(f"phi lays a head out by diagonals: {hd} is odd")
    return {"pr_state": CacheLeaf((groups, hd, expanded(hd)), ACTIVATIONS,
                                  whole=True),
            "pr_sum": CacheLeaf((groups, expanded(hd)), ACTIVATIONS,
                                whole=True),
            "stats": jax.ShapeDtypeStruct((len(STATS),), jnp.int32)}


def _dots(spec: str, x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.einsum(spec, x, y, precision=_EXACT,
                      preferred_element_type=jnp.float32)


def _decay(x: jax.Array) -> jax.Array:
    """exp(x) for x <= 0 where it is applied once: the chunked form's
    matrices (`decoder.exp_ulp` where it compounds)."""
    return 1.0 + jnp.expm1(x)


# -- power retention -------------------------------------------------------------

def phi(x: jax.Array) -> jax.Array:
    """[..., hd] -> [..., F]: the symmetric second power by diagonals
    (module docstring), `phi(q) . phi(k) = (q . k)^2`."""
    hd = x.shape[-1]
    return jnp.concatenate([
        x * (jnp.roll(x, d, axis=-1) if d else x)
        * retention_kernel.diagonal_weight(d, hd)
        for d in range(retention_kernel.diagonals(hd))], axis=-1)


def retention_step(q, k, v, a, state, zsum):
    """One position of the recurrence: q = q' [B, G, R, hd], k = k', v [B, G,
    hd], a [B, G], state [B, G, hd, F], zsum [B, G, F], float32, on the
    vector unit. -> (num [B, G, R, hd], den [B, G, R], state, zsum)."""
    keys, decay = phi(k), exp_ulp(a)
    state = state * decay[..., None, None] \
        + v[..., :, None] * keys[..., None, :]
    zsum = zsum * decay[..., None] + keys
    queries = phi(q)
    num = jnp.sum(state[:, :, None] * queries[..., None, :], axis=-1)
    return num, jnp.sum(queries * zsum[:, :, None], axis=-1), state, zsum


def _group_chunked(q, k, v, a, state, zsum):
    """`retention_chunked` of ONE KV head of some rows, laid out in chunks: q [N, B, C, R,
    hd], k, v [N, B, C, hd], a [N, B, C], state [B, hd, F], zsum [B, F]. ->
    (num [N, B, C, R, hd], den [N, B, C, R], state, zsum)."""
    chunk = a.shape[-1]
    at = jnp.arange(chunk)
    lower = at[:, None] >= at[None, :]

    def one_chunk(carry, xs):
        state, zsum = carry
        q_c, k_c, v_c, a_c = xs
        # the running sum as a product in full float32 (module docstring)
        run = _dots("bs,cs->bc", a_c, lower.astype(a_c.dtype))
        within = jnp.where(lower, _decay(jnp.where(
            lower, run[:, :, None] - run[:, None, :], 0.0)), 0.0)
        weights = _dots("bcrd,bsd->brcs", q_c, k_c) ** 2 * within[:, None]
        queries = phi(q_c)                                  # [B, C, R, F]
        since = _decay(run)[..., None]                      # [B, C, 1]
        num = _dots("brcs,bsv->bcrv", weights, v_c) \
            + since[..., None] * _dots("bcrf,bvf->bcrv", queries, state)
        den = jnp.moveaxis(jnp.sum(weights, axis=-1), 1, 2) \
            + since * _dots("bcrf,bf->bcr", queries, zsum)
        total = run[:, -1]
        keys = phi(k_c) * _decay(total[:, None] - run)[..., None]
        kept = exp_ulp(total)
        state = kept[:, None, None] * state + _dots("bcv,bcf->bvf", v_c, keys)
        zsum = kept[:, None] * zsum + jnp.sum(keys, axis=1)
        return (state, zsum), (num, den)

    (state, zsum), (num, den) = jax.lax.scan(one_chunk, (state, zsum),
                                             (q, k, v, a))
    return num, den, state, zsum


# bytes of a chunk's expanded queries (`phi(Q)`, float32) one cell of the
# chunked form holds at a time: as many rows of the batch as divide it and
# fit. Each (row, KV head) has its own state, so its products are its own
# whatever the cell ([C R, F] x [F, hd]): a cell of fewer rows feeds the
# matrix unit the same tiles, and the six-pass products keep several copies
# of their operands (8 rows a cell took 3.2 GB of temporaries beside 12.5 GB
# resident, the described chip's compiler, PR 58)
CELL_BYTES = 1 << 26


def cell_rows(rows: int, chunk: int, per_group: int, width: int) -> int:
    """Rows of the batch a cell of the chunked form holds (`CELL_BYTES`): a
    divisor of `rows`, at least one."""
    fit = max(1, CELL_BYTES // (chunk * per_group * width * 4))
    return max(d for d in range(1, min(rows, fit) + 1) if rows % d == 0)


def retention_chunked(q, k, v, a, state, zsum, chunk: int):
    """The recurrence over a span in chunks of `chunk` (module docstring), a
    cell (some rows of the batch, one KV head: `cell_rows`) after another: q
    = q' [B, S, G, R, hd], k = k', v [B, S, G, hd], a [B, S, G], state [B, G,
    hd, F], zsum [B, G, F], float32. A last chunk the span does not fill is
    padded with `a` = 0 and `k'` = 0, which leave the state. -> (num [B, S,
    G, R, hd], den [B, S, G, R], state, zsum)."""
    b, s, g = a.shape
    n = -(-s // chunk)
    rows = cell_rows(b, chunk, q.shape[3], state.shape[-1])
    cells = (b // rows) * g

    def lay(t):     # [B, S, G, ...] -> [cells, N, rows, C, ...], zeros past S
        t = jnp.pad(t, ((0, 0), (0, n * chunk - s)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((b // rows, rows, n, chunk) + t.shape[2:])
        t = jnp.moveaxis(t, (4, 2), (1, 2))     # [B / rows, G, N, rows, C, ..]
        return t.reshape((cells,) + t.shape[2:])

    def held(t):    # [B, G, ...] -> [cells, rows, ...]
        t = jnp.moveaxis(t.reshape((b // rows, rows) + t.shape[1:]), 2, 1)
        return t.reshape((cells,) + t.shape[2:])

    num, den, state, zsum = jax.lax.map(
        lambda xs: _group_chunked(*xs),
        (lay(q), lay(k), lay(v), lay(a), held(state), held(zsum)))

    def back(t):    # [cells, N, rows, C, ...] -> [B, S, G, ...]
        t = t.reshape((b // rows, g) + t.shape[1:])
        t = jnp.moveaxis(t, (1, 2), (4, 2))     # [B / rows, rows, N, C, G, ..]
        return t.reshape((b, n * chunk) + t.shape[4:])[:, :s]

    def kept(t):    # [cells, rows, ...] -> [B, G, ...]
        t = jnp.moveaxis(t.reshape((b // rows, g) + t.shape[1:]), 1, 2)
        return t.reshape((b, g) + t.shape[3:])

    return back(num), back(den), kept(state), kept(zsum)


def _kernel_mode():
    """How this backend runs the state kernel (`ops/retention_step.py`):
    "mosaic" on a TPU, None where Mosaic cannot run (`retention_step` serves
    every call); the tests put "interpret" here."""
    return "mosaic" if jax.default_backend() == "tpu" else None


def state_kernel_mode(bcache, span: int, prefill: bool):
    """How a block's call moves its state on, read off the call:
    `_kernel_mode()` where the kernel updates the layer where it lies in the
    cache's stack (a step, over what the cache holds, of a float32 leaf that
    the decode driver has its blocks write in place, `LayerCache.placed`;
    compiled, a head of whole rows of lanes: interpret mode knows none);
    None where `retention_step` or `retention_chunked` hand back the rows'
    state for the driver to write."""
    stack = bcache.stack["pr_state"]
    mode = _kernel_mode()
    fits = span == 1 and not prefill and "pr_state" in bcache.placed \
        and stack.dtype == jnp.float32 \
        and (mode == "interpret"
             or retention_kernel.whole_tiles(stack.shape[3]))
    return mode if fits else None


def _heads(p: Dict, name: str, normed, heads: int, hd: int):
    b, s, _ = normed.shape
    return in_row_chunks(lambda rows: lin(p[name]["w"], rows), normed,
                         heads * hd).reshape(b, s, heads, hd)


def retention(p: Dict, normed, bcache, pos, cfg: TransformerConfig,
              prefill: bool):
    """The mixer of `normed` [B, S, D] at [pos, pos + S) over the cache: its
    state and sum of keys from the cache (a prefill, at `pos` 0: zeros), and
    what they are after the span recorded as rows, which take their place; a
    step whose state the kernel has updated in the stack
    (`state_kernel_mode`) records no state and hands back the cache with
    that stack. -> (out [B, S, D], the cache, its rows, what the call counts
    under `STATS`)."""
    b, s, _ = normed.shape
    heads, groups, hd = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    eps, scale = cfg.layer_norm_eps, hd ** -0.25
    stack, layer = bcache.stack, bcache.layer
    at = jnp.asarray(pos) + jnp.arange(s)
    freqs = rope_frequencies(hd, cfg.rope_theta)
    q = rotate_halves(rms_norm(p["q_norm"], _heads(p, "q", normed, heads, hd),
                               eps), at, freqs) * scale
    k = rotate_halves(rms_norm(p["k_norm"], _heads(p, "k", normed, groups,
                                                   hd), eps), at, freqs) * scale
    v = _heads(p, "v", normed, groups, hd)
    a = jax.nn.log_sigmoid(lin(p["gate"]["w"], normed))         # [B, S, G]
    q = q.reshape(b, s, groups, heads // groups, hd)

    def read(name):
        got = jax.lax.dynamic_index_in_dim(stack[name], layer, 0,
                                           keepdims=False)
        return jnp.zeros_like(got) if prefill else got

    mode = state_kernel_mode(bcache, s, prefill)
    counts = jnp.array([b * s if s > 1 else 0, b if s == 1 else 0,
                        b if mode else 0], jnp.int32)
    if mode:
        placed, zsum, num, den = retention_kernel.step(
            stack["pr_state"], layer, exp_ulp(a[:, 0]), read("pr_sum"),
            q[:, 0], k[:, 0], v[:, 0], interpret=mode == "interpret")
        bcache = bcache._replace(stack=dict(stack, pr_state=placed))
        rows = {"pr_sum": zsum}
    else:
        state, zsum = (read(name).astype(jnp.float32)
                       for name in ("pr_state", "pr_sum"))
        if s == 1:
            num, den, state, zsum = retention_step(
                q[:, 0], k[:, 0], v[:, 0], a[:, 0], state, zsum)
        else:
            num, den, state, zsum = retention_chunked(
                q, k, v, a, state, zsum, min(cfg.linear_chunk, s))
        rows = {"pr_state": state, "pr_sum": zsum}
    # a step's [B, G, R, ...] and a span's [B, S, G, R, ...] alike
    y = (num / (den[..., None] + EPS)).reshape(b, s, heads * hd)
    return lin(p["attn_out"]["w"], y.astype(normed.dtype)), bcache, rows, \
        counts


# -- the family's hooks --------------------------------------------------------

def cached_block_step(p: Dict, x, bcache, pos, cfg: TransformerConfig,
                      prefill: bool, read_len=None):
    """Cached block (the decode driver's `_block_step` contract): the mixer,
    then the SwiGLU, each after its norm. The rows of `x` sit at [pos, pos +
    S). `read_len` is the one width the stage binds (module docstring,
    Cache): nothing here reads a window."""
    del read_len
    eps = cfg.layer_norm_eps
    mixed, bcache, rows, counts = retention(
        p, rms_norm(p["ln_before"], x, eps), bcache, pos, cfg, prefill)
    h = x + mixed
    out = h + decoder.dense_ffn(p["mlp"], rms_norm(p["ln_after"], h, eps))
    return out, bcache._replace(rows=dict(rows, stats=counts))


# positions live in the rotation of q and k
FAMILY = FamilySpec(name="brumby", cached_block_step=cached_block_step,
                    **decoder.token_hooks("brumby", ACTIVATIONS, rms_norm),
                    decoder_model=True, position_dependent_attention=True,
                    cache_leaves=cache_leaves, prefill_span=prefill_span,
                    stats_names=STATS)


# -- loading -------------------------------------------------------------------

def _assemble(cfg: TransformerConfig, shard_config: ShardConfig, get,
              dtype) -> Dict:
    """Shard params from `get(key, shape)`, a tensor of the published
    scheme (module docstring; `decoder.loader`, `assemble_shard`); the
    gate's projection stays float32."""
    d, heads, groups, hd = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.kv_heads, cfg.head_dim
    cache_leaves(cfg)

    def scale(key, n):
        return {"scale": get(key, (n,))}

    def get_embed() -> Dict:
        return {"wte": get("model.embed_tokens.weight", (cfg.vocab_size, d))}

    def get_block(block_id: int, subs: tuple) -> Dict:
        decoder.whole_blocks("brumby", subs)
        root = f"model.layers.{block_id}."
        att = root + "self_attn."
        return {
            "ln_before": scale(root + "input_layernorm.weight", d),
            "q": {"w": get(att + "q_proj.weight", (heads * hd, d))},
            "k": {"w": get(att + "k_proj.weight", (groups * hd, d))},
            "v": {"w": get(att + "v_proj.weight", (groups * hd, d))},
            "gate": {"w": get(att + "g_proj.weight", (groups, d))},
            "q_norm": scale(att + "q_norm.weight", hd),
            "k_norm": scale(att + "k_norm.weight", hd),
            "attn_out": {"w": get(att + "o_proj.weight", (d, heads * hd))},
            "ln_after": scale(root + "post_attention_layernorm.weight", d),
            "mlp": {name: get(f"{root}mlp.{name}_proj.weight", shape)
                    for name, shape in (
                        ("gate", (cfg.intermediate_size, d)),
                        ("up", (cfg.intermediate_size, d)),
                        ("down", (d, cfg.intermediate_size)))}}

    def get_final() -> Dict:
        return {"ln": scale("model.norm.weight", d),
                "head": {"w": get("lm_head.weight", (cfg.vocab_size, d))}}

    return decoder.assemble_shard(shard_config, get_embed, get_block,
                                  get_final, dtype,
                                  float32=(("gate", "w"),))


load_params, init_params = decoder.loader(_assemble)
