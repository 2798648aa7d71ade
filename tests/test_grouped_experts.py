"""The expert layer's grouped kernel (`ops/grouped_matmul.py`: up, activation
and down in one walk of the touched experts) against its tile loop
(`parallel/expert.py`): the same call both ways, the kernel in interpret
mode, the kernel alone against `_expert_ffn` a group, and the rule that
says which calls take which.

The CPU backend keeps every call on the loop (`expert._grouped_mode` is None
here); the tests put "interpret" there. What Mosaic makes of the cells'
widths is `tests/test_chip_compile.py`'s to ask."""
import collections
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from pipeedge_tpu.models import registry
from pipeedge_tpu.models.layers import TransformerConfig, _three_parts
from pipeedge_tpu.ops import grouped_matmul
from pipeedge_tpu.parallel import expert
from pipeedge_tpu.utils import jax_compat

# both ways are float32 sums of exact products in another order (1.2e-7 of
# the range measured); a single bfloat16 pass is 2e-3 off and must fail
TOLERANCE = 1e-5

# family -> a step's call at tiny widths: (rows, experts, top-k, held
# (first, count) or None, router, expert width F, hidden D, a shared expert)
STEPS = {
    "lfm2": (16, 8, 4, None, "sigmoid", 48, 32, False),
    "laguna": (4, 32, 8, None, "softmax", 16, 32, True),
    "qwen3-next": (2, 32, 10, (0, 16), "softmax", 16, 32, True),
    "keye": (2, 16, 8, None, "softmax", 24, 32, False),
    "kimi": (8, 48, 8, (0, 6), "sigmoid", 32, 64, True),
    # experts without a gate matrix in a latent (`LATENT`), 5.5 tokens each
    "nemotron-h": (16, 64, 22, (0, 16), "sigmoid", 24, 64, True),
}

# family -> the width of the latent its routed experts read and write
LATENT = {"nemotron-h": 32}

# cell -> (model, rows of a step, tokens of a span)
CELLS = {
    "lfm2.extract-batch": ("LiquidAI/LFM2-8B-A1B@12", 128, 128 * 128),
    "laguna-xs2.repo-batch": ("poolside/Laguna-XS.2@5", 32, 32 * 128),
    "qwen3-next.longdoc-batch": (
        "Qwen/Qwen3-Next-80B-A3B-Instruct@4,e0+256,v75968", 8, 8 * 1024),
    "keye-vl2.long-batch": ("Kwai-Keye/Keye-VL-2.0-30B-A3B@6", 8, 8 * 512),
    "kimi-k2.agent-batch": ("moonshotai/Kimi-K2-Instruct@5,e0+12,v20480", 32,
                            32 * 128),
    "nemotron3-super.reason-batch": (
        "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16@11,e0+128,v32768",
        128, 128 * 64),
}


@pytest.fixture
def grouped(monkeypatch):
    monkeypatch.setattr(expert, "_grouped_mode", lambda: "interpret")


def _layer(experts, per_tok, held, router, f, d, shared, dtype, stack=None,
           seed=0, latent=0):
    """`latent`: the routed experts are `down(relu(up c)^2)` over a latent
    of that width (no gate matrix); 0: SwiGLUs on the model's width."""
    rng = np.random.default_rng(seed)
    cfg = TransformerConfig(
        model_type="tiny", hidden_size=d, num_hidden_layers=1,
        num_attention_heads=1, intermediate_size=f, n_experts=experts,
        num_experts_per_tok=per_tok, moe_intermediate_size=f, router=router,
        norm_topk_prob=True, held_experts=held or (),
        moe_latent_size=latent, expert_act="relu2" if latent else "silu")
    lead = (held[1] if held else experts,)
    if stack:
        lead = (stack,) + lead

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / math.sqrt(shape[-1]),
                           jnp.float32).astype(dtype)
    params = {"router": {"w": jnp.asarray(rng.normal(size=(d, experts)),
                                          jnp.float32)},
              "experts": {"gate": w(*lead, f, d), "up": w(*lead, f, d),
                          "down": w(*lead, d, f)}}
    if router == "sigmoid":
        params["router"]["bias"] = jnp.asarray(
            rng.normal(size=(experts,)) / 16, jnp.float32)
    if shared:
        params["shared"] = {"gate": w(f, d), "up": w(f, d), "down": w(d, f)}
    if latent:
        params["latent"] = {"down": w(latent, d), "up": w(d, latent)}
        params["experts"] = {"up": w(*lead, f, latent),
                             "down": w(*lead, latent, f)}
        params.get("shared", {}).pop("gate", None)
    return cfg, params


def _rows(rows, d, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(rows, 1, d)),
                       jnp.float32)


def _both_ways(cfg, params, x, monkeypatch, **kwargs):
    """(the loop's delta and counts, the grouped kernel's)."""
    monkeypatch.setattr(expert, "_grouped_mode", lambda: None)
    loop = jax.jit(lambda p, y: expert.topk_ffn_delta(p, y, cfg, **kwargs))(
        params, x)
    monkeypatch.setattr(expert, "_grouped_mode", lambda: "interpret")
    kernel = jax.jit(lambda p, y: expert.topk_ffn_delta(p, y, cfg, **kwargs))(
        params, x)
    return loop, kernel


def _close(got, wanted):
    assert np.all(np.isfinite(got))
    span = float(np.max(wanted) - np.min(wanted))
    np.testing.assert_allclose(got, wanted, rtol=0, atol=TOLERANCE * span)


@pytest.mark.parametrize("weights", ["bfloat16", "float32"])
@pytest.mark.parametrize("family", sorted(STEPS))
def test_the_grouped_kernels_are_the_loop(family, weights, monkeypatch):
    rows, *shape = STEPS[family]
    cfg, params = _layer(*shape, jnp.dtype(weights),
                         latent=LATENT.get(family, 0))
    (wanted, loop), (got, kernel) = _both_ways(cfg, params,
                                               _rows(rows, shape[5]),
                                               monkeypatch)
    _close(got, wanted)
    # the same assignments and experts; the kernel says it ran
    assert loop[0] == kernel[0] and loop[2] == kernel[2]
    assert (loop[3], kernel[3]) == (0, 1)


def test_a_single_bfloat16_pass_would_fail_the_tolerance(monkeypatch):
    """What the tolerance is for: rows rounded to bfloat16 in the kernel
    (one part where `exact_dot` has three) are a hundred times off."""
    rows, *shape = STEPS["lfm2"]
    cfg, params = _layer(*shape, jnp.bfloat16)
    x = _rows(rows, shape[5])
    (wanted, _), _ = _both_ways(cfg, params, x, monkeypatch)
    monkeypatch.setattr(grouped_matmul, "row_parts",
                        lambda rows, dtype: (1, rows.astype(dtype)))
    got, _ = jax.jit(lambda p, y: expert.topk_ffn_delta(p, y, cfg))(params, x)
    span = float(np.max(wanted) - np.min(wanted))
    assert np.max(np.abs(got - wanted)) > 20 * TOLERANCE * span


def test_bfloat16_rows_take_one_part(monkeypatch):
    rows, *shape = STEPS["keye"]
    cfg, params = _layer(*shape, jnp.bfloat16)
    x = _rows(rows, shape[5]).astype(jnp.bfloat16)
    (wanted, _), (got, _) = _both_ways(cfg, params, x, monkeypatch)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(wanted, np.float32), atol=2e-2)


@pytest.mark.parametrize("layer", [0, 2])
def test_a_traced_layer_indexes_the_stack_where_it_lies(layer, monkeypatch):
    rows, *shape = STEPS["laguna"]
    cfg, params = _layer(*shape, jnp.bfloat16, stack=3)
    x = _rows(rows, shape[5])
    one = dict(params, experts={name: leaf[layer] for name, leaf
                                in params["experts"].items()})
    monkeypatch.setattr(expert, "_grouped_mode", lambda: None)
    wanted, _ = expert.topk_ffn_delta(one, x, cfg)
    monkeypatch.setattr(expert, "_grouped_mode", lambda: "interpret")
    step = jax.jit(lambda p, y, at: expert.topk_ffn_delta(p, y, cfg,
                                                          layer=at))
    jaxpr = jax.make_jaxpr(lambda p, y, at: expert.topk_ffn_delta(
        p, y, cfg, layer=at))(params, x, 1)
    got, _ = step(params, x, layer)
    _close(got, wanted)
    # no layer's experts are taken out of the stack: nothing the program
    # makes is as large as one layer's gate matrices
    a_layer = math.prod(params["experts"]["gate"].shape[1:])
    made = [math.prod(var.aval.shape) for eqn in jaxpr.jaxpr.eqns
            for var in eqn.outvars if eqn.primitive.name != "reshape"]
    assert max(made) < a_layer


def test_shares_with_a_traced_first_add_up_to_the_layer(monkeypatch):
    """Four devices hold four experts each under `shard_map`, `first` the
    device's index times four: the parts add up to the loop's whole layer,
    and what a device's kernels leave unwritten (every row of another
    device's experts) never reaches the sum."""
    rows, experts, per_tok, _, router, f, d, _ = STEPS["keye"]
    cfg, params = _layer(experts, per_tok, None, router, f, d, False,
                         jnp.bfloat16)
    x = _rows(rows, d)
    wanted, loop = expert.topk_ffn_delta(params, x, cfg)
    monkeypatch.setattr(expert, "_grouped_mode", lambda: "interpret")
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("ep",))
    specs = {"router": {"w": P()},
             "experts": {name: P("ep") for name in ("gate", "up", "down")}}
    body = jax_compat.shard_map(
        lambda p, y: expert.ep_topk_ffn_delta(p, y, cfg, "ep"), mesh=mesh,
        in_specs=(specs, P()), out_specs=(P(), P()))
    got, counts = jax.jit(body)(params, x)
    _close(got, wanted)
    assert counts[0] == loop[0] == rows * per_tok and counts[3] == 4


def _biased(params, bias):
    router = dict(params["router"], bias=jnp.asarray(bias, jnp.float32))
    return dict(params, router=router)


def test_empty_groups_and_tokens_of_other_chips_cost_nothing(monkeypatch):
    """The bias sends every token to experts 8 to 11 of 16; this caller
    holds 6 to 9: two of its four groups are empty, half of every token's
    assignments are another chip's, and one caller (experts 0 to 3) is
    given nothing at all."""
    cfg, params = _layer(16, 4, (6, 4), "sigmoid", 24, 32, False,
                         jnp.bfloat16)
    params = _biased(params, [0.0] * 8 + [9.0] * 4 + [0.0] * 4)
    x = _rows(6, 32)
    mine = dict(params, experts={name: leaf[:4] for name, leaf
                                 in params["experts"].items()})
    (wanted, loop), (got, kernel) = _both_ways(cfg, mine, x, monkeypatch)
    _close(got, wanted)
    assert kernel[0] == 6 * 2 and kernel[2] == 2
    # two groups of six rows in one row tile of 16: two visits
    assert kernel[1] == 2 * 16 and loop[1] == 2 * 8
    nothing = dataclasses.replace(cfg, held_experts=(0, 4))
    got, kernel = jax.jit(lambda p, y: expert.topk_ffn_delta(p, y, nothing))(
        mine, x)
    assert not np.any(np.asarray(got)) and not np.any(np.asarray(kernel[:3]))


@pytest.mark.parametrize("aligned", [False, True])
def test_a_group_above_the_row_tile_takes_several_visits(aligned,
                                                         monkeypatch):
    """Every row chooses expert 3 first: its group is 40 rows where the
    call's row tile is 16, the groups packed or each on a tile of its
    own."""
    cfg, params = _layer(8, 2, None, "sigmoid", 24, 32, False, jnp.bfloat16)
    params = _biased(params, [0.0] * 3 + [9.0] + [0.0] * 4)
    x = _rows(40, 32)
    assert expert.grouped_layout(expert.expert_tile(40, 2, 8)) == (32, True)
    monkeypatch.setattr(expert, "grouped_layout", lambda tile: (16, aligned))
    (wanted, _), (got, kernel) = _both_ways(cfg, params, x, monkeypatch)
    _close(got, wanted)
    # 80 sorted rows are five row tiles; packed, the groups after expert
    # 3's start inside tiles that another group has visited; aligned, no
    # tile is visited twice and expert 3's 40 rows take three
    sizes = np.bincount(np.asarray(expert.topk_route(
        params["router"], x.reshape(40, 32), cfg)[0]).reshape(-1),
        minlength=8)
    assert kernel[0] == 80 and sizes[3] == 40
    if aligned:
        assert kernel[1] == 16 * np.sum(-(-sizes // 16))
    else:
        assert kernel[1] >= 5 * 16


def test_the_way_back_selects_rows_it_never_multiplies_by_zero():
    """A row no group owns may hold anything (the kernels never write it):
    the way back must not let it through, which a zero gate times a NaN
    would."""
    out = jnp.full((8, 4), jnp.nan).at[:3].set(1.0)
    sorted_at = jnp.asarray([[2, 6], [3, 7], [4, 0]])
    gates = jnp.asarray([[0.5, 0.0], [0.25, 0.0], [1.0, 0.0]])
    delta = expert._back_to_tokens(jnp.zeros((3, 4)), out, sorted_at, gates,
                                   2, 5)
    np.testing.assert_array_equal(delta, np.asarray(
        [[0.5] * 4, [0.25] * 4, [1.0] * 4]))


def test_the_kernel_leaves_unowned_rows_alone_and_the_layer_is_finite(
        grouped):
    """In interpret mode a fresh output buffer is NaN: a share's call, most
    of whose sorted rows are other chips', still gives finite numbers."""
    rows, *shape = STEPS["kimi"]
    cfg, params = _layer(*shape, jnp.bfloat16)
    got, counts = expert.topk_ffn_delta(params, _rows(rows, shape[5]), cfg)
    assert counts[0] < rows * shape[1] and np.all(np.isfinite(got))
    bounds = jnp.asarray([0, 3, 3, 7])
    items = grouped_matmul.group_items(bounds[:-1], bounds[1:], 0, 16, 3)
    out = grouped_matmul.grouped_ffn(
        jnp.ones((16, 8)), (jnp.ones((3, 8, 8)), jnp.ones((3, 8, 8))), items,
        row_tile=16, act=expert.ACTS["relu2"], interpret=True)
    # eight hidden values of 8 squared, added up
    assert np.all(np.asarray(out[:7]) == 512) and np.all(np.isnan(out[7:]))


def _kernel_alone(sizes, row_tile, f, k, d, form, weights, rows_dtype,
                  seed=3):
    """The kernel over packed groups of `sizes` rows -> (its rows, what
    `_expert_ffn` gives each group's rows, which rows a group owns)."""
    rng = np.random.default_rng(seed)
    act, gated = form
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    n_rows = -(-int(bounds[-1]) // row_tile) * row_tile

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / math.sqrt(shape[-1]),
                           jnp.float32).astype(weights)
    ex = {"up": w(len(sizes), f, k), "down": w(len(sizes), d, f)}
    if gated:
        ex["gate"] = w(len(sizes), f, k)
    x = jnp.asarray(rng.normal(size=(n_rows, k)), rows_dtype)
    items = grouped_matmul.group_items(
        jnp.asarray(bounds[:-1]), jnp.asarray(bounds[1:]), 0, row_tile,
        grouped_matmul.max_items(n_rows, len(sizes), row_tile))
    got = grouped_matmul.grouped_ffn(
        x, [ex[name] for name in expert.expert_names(ex)], items,
        row_tile=row_tile, act=expert.ACTS[act], interpret=True)
    wanted = np.full((n_rows, d), np.nan, np.float32)
    for g in range(len(sizes)):
        wanted[bounds[g]:bounds[g + 1]] = expert._expert_ffn(
            x[bounds[g]:bounds[g + 1]],
            {name: leaf[g] for name, leaf in ex.items()}, act)
    return np.asarray(got), wanted, ~np.isnan(wanted[:, 0])


@pytest.mark.parametrize("weights, rows_dtype", [
    ("bfloat16", "float32"), ("float32", "float32"),
    ("bfloat16", "bfloat16")])
@pytest.mark.parametrize("form", [("relu2", False), ("silu", False),
                                  ("silu", True)])
def test_the_kernel_is_an_experts_ffn_in_each_of_its_forms(form, weights,
                                                           rows_dtype):
    """Experts of two matrices under either activation and SwiGLUs, in
    `exact_dot`'s three cases: three parts, `HIGHEST`, one narrow pass (the
    hidden rounded to the rows' bfloat16 between the products, as
    `_expert_ffn` rounds it)."""
    got, wanted, owned = _kernel_alone(
        [5, 0, 16, 3], 16, 48, 32, 40, form, jnp.dtype(weights),
        jnp.dtype(rows_dtype))
    assert np.sum(owned) == 24
    span = float(np.max(wanted[owned]) - np.min(wanted[owned]))
    np.testing.assert_allclose(
        got[owned], wanted[owned], rtol=0,
        atol=(2e-2 if rows_dtype == "bfloat16" else TOLERANCE) * span)


@pytest.mark.parametrize("blocks", [1, 2, 4])
def test_three_groups_in_one_packed_tile_each_keep_their_rows(blocks,
                                                              monkeypatch):
    """Groups of 5, 4 and 6 rows in one row tile of 16 (three visits, then
    a fourth group in the next tile), the hidden of 512 whole and cut in
    two and four blocks: a visit's blocks add up in the output block where
    it lies, and the rows that earlier visits of the tile wrote stay."""
    f, k, d = 512, 128, 256
    monkeypatch.setattr(grouped_matmul, "BLOCK_BYTES",
                        (f // blocks) * (k + d) * 2)
    assert grouped_matmul.column_block(f, k + d, 2) == f // blocks
    got, wanted, owned = _kernel_alone(
        [5, 4, 6, 9], 16, f, k, d, ("relu2", False), jnp.bfloat16,
        jnp.float32)
    assert list(np.flatnonzero(~owned)) == list(range(24, 32))
    _close(got[owned], wanted[owned])
    assert np.all(np.isnan(got[~owned]))


def test_a_hidden_cut_in_blocks_is_the_whole_one_reordered(monkeypatch):
    """The one difference the blocks may make: `down`'s float32 sums a
    block at a time. Against the hidden whole that is a few units in the
    last place of the largest value, and nothing like a lost part."""
    f, k, d = 512, 128, 128
    shape = ([7, 16, 2], 16, f, k, d, ("silu", True), jnp.bfloat16,
             jnp.float32)
    whole, _, owned = _kernel_alone(*shape)
    monkeypatch.setattr(grouped_matmul, "BLOCK_BYTES", 128 * (2 * k + d) * 2)
    assert grouped_matmul.column_block(f, 2 * k + d, 2) == 128
    cut, _, _ = _kernel_alone(*shape)
    span = float(np.max(whole[owned]) - np.min(whole[owned]))
    gap = float(np.max(np.abs(cut[owned] - whole[owned])))
    assert 0 < gap < 1e-6 * span


@pytest.mark.parametrize("scale", [1.0, 1e-30, 3e38, 2.0 ** -130])
def test_the_parts_made_in_the_kernel_are_three_parts_bit_for_bit(scale):
    """`row_parts` rounds on the bits what `_three_parts` rounds with
    `reduce_precision`: the same three bfloat16 values a number, inside a
    kernel and outside, at the ends of the range too (a part that rounds up
    to infinity, subnormal remainders), and ties go to even."""
    from jax.experimental import pallas as pl
    x = np.random.default_rng(7).normal(size=(16, 256)).astype(np.float32)
    x[0, :4] = [1.00390625, 1.01171875, -1.00390625, 0.0]   # ties
    with np.errstate(over="ignore"):      # 3e38: some of them infinite
        x = jnp.asarray(x * np.float32(scale))
    wanted = np.asarray(_three_parts(x, jnp.bfloat16).astype(jnp.float32))

    def kernel(x_ref, o_ref):
        o_ref[...] = grouped_matmul.row_parts(x_ref[...], jnp.bfloat16)[1]
    in_kernel = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((48, 256), jnp.bfloat16),
        interpret=True)(x)
    parts, outside = jax.jit(
        lambda y: grouped_matmul.row_parts(y, jnp.bfloat16))(x)
    assert parts == 3
    for got in (in_kernel, outside):
        got = np.asarray(got.astype(jnp.float32)).reshape(3, 16, 256)
        np.testing.assert_array_equal(got.view(np.uint32),
                                      wanted.view(np.uint32))
    if scale == 1.0:    # three parts are three: the second is not zero
        assert np.count_nonzero(wanted[1]) > 4000
        assert np.all(wanted[0, 0, :3] == [1.0, 1.015625, -1.0])


@pytest.mark.parametrize("sizes, row_tile", [
    ([3, 0, 20, 1, 0, 7], 16), ([0, 0, 0], 16), ([16, 16, 16], 16),
    ([1] * 40, 16), ([100], 32), ([0, 5, 0, 0, 70, 2], 32)])
def test_the_work_list_is_every_tile_a_group_has_rows_in(sizes, row_tile):
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    wanted = [(7 + g, t, bounds[g], bounds[g + 1])
              for t in range(-(-int(bounds[-1]) // row_tile))
              for g in range(len(sizes))
              if max(bounds[g], t * row_tile)
              < min(bounds[g + 1], (t + 1) * row_tile)]
    n = grouped_matmul.max_items(int(bounds[-1]) or 1, len(sizes), row_tile)
    assert len(wanted) <= n
    items = grouped_matmul.group_items(jnp.asarray(bounds[:-1]),
                                       jnp.asarray(bounds[1:]), 7, row_tile, n)
    count = int(items.count)
    assert count == max(len(wanted), 1)
    got = list(zip(*(np.asarray(part)[:count].tolist()
                     for part in items[:4])))
    if wanted:
        assert got == [tuple(int(v) for v in item) for item in wanted]
    else:           # one item that owns no row keeps the grid from empty
        assert got[0][2] == got[0][3]
    # past the count every item owns nothing
    assert np.all(np.asarray(items.start)[count:]
                  == np.asarray(items.end)[count:])


@pytest.mark.parametrize("shape", [(7,), (3, 4), ()])
def test_short_tables_are_read_by_comparison(shape):
    rng = np.random.default_rng(11)
    table = np.sort(rng.integers(0, 50, size=9))
    index = rng.integers(0, 9, size=shape)
    np.testing.assert_array_equal(
        grouped_matmul.pick(jnp.asarray(table), jnp.asarray(index)),
        table[index])
    values = rng.integers(-3, 55, size=shape)
    np.testing.assert_array_equal(
        grouped_matmul.count_up_to(jnp.asarray(table), jnp.asarray(values)),
        np.searchsorted(table, values, side="right"))


@pytest.mark.parametrize("n, k, wanted", [
    # the six cells' hidden, a column of it `gate`'s, `up`'s and `down`'s:
    # lfm2, laguna and qwen3-next, keye, kimi (the one that is cut), nemotron
    (1792, 3 * 2048, 1792), (512, 3 * 2048, 512), (768, 3 * 2048, 768),
    (2048, 3 * 7168, 512), (2688, 2 * 1024, 2688),
    (7168, 3 * 2048, 1792), (2048, 6 * 7168, 256), (48, 32, 48)])
def test_a_matrix_block_is_whole_lanes_under_the_block_bytes(n, k, wanted):
    block = grouped_matmul.column_block(n, k, 2)
    assert block == wanted and n % block == 0
    assert block == n or (block % 128 == 0
                          and block * k * 2 <= grouped_matmul.BLOCK_BYTES)


def _cell_call(cell, tokens):
    model, _, _ = CELLS[cell]
    cfg = registry.get_model_entry(model).config
    return cfg, expert.expert_tile(tokens, cfg.num_experts_per_tok,
                                   cfg.n_experts)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cells_step_is_under_the_ridge_and_its_span_above(cell):
    _, step, span = CELLS[cell]
    assert _cell_call(cell, step)[1] <= expert.GROUPED_RIDGE
    assert _cell_call(cell, span)[1] > expert.GROUPED_RIDGE
    # the served path's one row, too
    assert _cell_call(cell, 1)[1] <= expert.GROUPED_RIDGE


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_rule_is_monotone_in_the_tokens(cell):
    """Once a call's tile passes the ridge no larger call comes back under
    it; and the kernel's row tile is whole sublane tiles that hold the
    call's loop tile."""
    tiles = [_cell_call(cell, tokens)[1] for tokens in range(1, 4097, 7)]
    assert tiles == sorted(tiles)
    for tile in set(tiles):
        rows, aligned = expert.grouped_layout(tile)
        assert rows % 16 == 0 and tile <= rows < tile + 16
        # groups of a row or two are packed, groups near a row tile aligned
        assert aligned == (tile > 8)


def _primitives(jaxpr, names):
    """Equations by primitive, those that make one value left out (an
    index read off a vector is a `dynamic_slice` too)."""
    for eqn in jaxpr.eqns:
        if max(math.prod(var.aval.shape) for var in eqn.outvars) > 1:
            names[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, names)
    return names


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_step_walks_its_groups_in_kernels_and_a_span_in_the_loop(
        cell, monkeypatch):
    """At the cell's real widths (shapes only): the step's program holds
    one kernel and no loop, no slice of the stack, no row update; the
    span's holds the tile loop and no kernel. On a backend without Mosaic
    both hold the loop."""
    model, step, span = CELLS[cell]
    cfg = registry.get_model_entry(model).config
    held = cfg.held_experts[1] if cfg.held_experts else cfg.n_experts
    d, f = cfg.hidden_size, cfg.moe_intermediate_size

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    params = {"router": {"w": jax.ShapeDtypeStruct((d, cfg.n_experts),
                                                   jnp.float32)},
              "experts": {"gate": leaf(4, held, f, d),
                          "up": leaf(4, held, f, d),
                          "down": leaf(4, held, d, f)}}
    if cfg.moe_latent_size:     # two matrices an expert, in the latent
        wide = cfg.moe_latent_size
        params.update(latent={"down": leaf(wide, d), "up": leaf(d, wide)},
                      experts={"up": leaf(4, held, f, wide),
                               "down": leaf(4, held, wide, f)})
    if cfg.router == "sigmoid":
        params["router"]["bias"] = jax.ShapeDtypeStruct((cfg.n_experts,),
                                                        jnp.float32)

    def traced(tokens):
        jaxpr = jax.make_jaxpr(lambda p, y, at: expert.topk_ffn_delta(
            p, y, cfg, layer=at))(
                params, jax.ShapeDtypeStruct((tokens, 1, d), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32))
        return _primitives(jaxpr.jaxpr, collections.Counter())

    on_cpu = traced(step)
    assert on_cpu["while"] == 2 and on_cpu["pallas_call"] == 0
    monkeypatch.setattr(expert, "_grouped_mode", lambda: "mosaic")
    names = traced(step)
    assert names["pallas_call"] == 1
    assert not (names["while"] or names["dynamic_slice"]
                or names["dynamic_update_slice"])
    # the loop's scans are two binary searches over index vectors (the
    # groups' bounds, a tile's expert); a step counts by comparison
    assert (on_cpu["scan"], names["scan"]) == (2, 0)
    names = traced(span)
    assert names["while"] == 2 and names["pallas_call"] == 0


def test_the_counter_reads_every_step_and_no_span(grouped, monkeypatch):
    """A tiny lfm2 batch through the pipeline: `moe_grouped_calls` is the
    decode phase's layer calls and none of the prefill's (spans of 8 rows x
    2 are above no ridge at tiny size, so the ridge is put between them)."""
    from pipeedge_tpu.parallel import decode
    from pipeedge_tpu.telemetry import metrics as prom
    from pipeedge_tpu.models import lfm2

    def counters():
        return {(name, phase): prom.REGISTRY.counter(
            f"pipeedge_{name}_total", "").value(phase=phase)
            for name in lfm2.STATS for phase in ("prefill", "decode")}
    cfg = registry.get_model_entry("pipeedge/test-tiny-lfm2").config
    step_tile, span_tile = (
        expert.expert_tile(tokens, cfg.num_experts_per_tok, cfg.n_experts)
        for tokens in (2, 2 * lfm2.prefill_span(cfg)))
    assert step_tile < span_tile
    monkeypatch.setattr(expert, "GROUPED_RIDGE", step_tile)
    pipe = decode.build_decode_pipeline("pipeedge/test-tiny-lfm2", None,
                                        max_len=32, dtype=jnp.float32)
    ids = np.random.default_rng(5).integers(0, 50, size=(2, 16))
    before = counters()
    pipe.generate(ids, 5)
    gained = {key: value - before[key] for key, value in counters().items()}
    assert gained["moe_layer_calls", "prefill"] > 0
    assert gained["moe_grouped_calls", "prefill"] == 0
    assert gained["moe_grouped_calls", "decode"] \
        == gained["moe_layer_calls", "decode"] > 0
