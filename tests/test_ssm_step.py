"""The in-place Mamba-2 state kernel (`ops/ssm_step.py`) in interpret mode
against the jnp step it stands in for (`models/nemotron_h.py::ssm_step`) on a
stack of three layers: the layer it is pointed at moves one position on, and
no other byte of the stack changes.

The CPU backend keeps every step on the jnp (`nemotron_h._kernel_mode` is
None there); `tests/test_nemotron_h.py` puts "interpret" there for the whole
decode path and `tests/test_chip_compile_families.py` compiles the cell's
step program with the kernel for the described chip.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu.models import nemotron_h
from pipeedge_tpu.models.decoder import exp_ulp
from pipeedge_tpu.ops import ssm_step

LAYERS = 3


def _inputs(rows, groups, per, hd, n, seed):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    stack = draw(LAYERS, rows, groups * per, hd, n)
    x, bm, cm = draw(rows, groups, per, hd), draw(rows, groups, n), \
        draw(rows, groups, n)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (rows, groups, per)), jnp.float32)
    la = -dt * jnp.asarray(rng.uniform(1.0, 16.0, (groups, per)), jnp.float32)
    return stack, x, bm, cm, dt, la


def _close(got, wanted):
    wanted = np.asarray(wanted)
    spread = float(wanted.max() - wanted.min())
    assert float(np.abs(np.asarray(got) - wanted).max()) <= 1e-6 * spread


# (rows, groups, heads a group, P, N): a group of 16 heads of 64 x 128 is
# 512 KB, so `BLOCK_BYTES` holds four rows a grid cell: 8 rows are two whole
# cells, 6 are two of three, 5 are five of one; 3 heads a group are no whole
# sublane tile, 8 x 8 no whole tile of a head's state (interpret mode only)
SHAPES = {"rows-8-of-4": (8, 1, 16, 64, 128),
          "rows-6-of-3": (6, 1, 16, 64, 128),
          "rows-5-of-1": (5, 1, 16, 64, 128),
          "heads-3-a-group": (2, 2, 3, 8, 128),
          "the-tiny-twins": (2, 2, 2, 8, 8)}


DECAYS = ("drawn", "one", "zero")

# the small shapes at every layer and decay, the large at each of both once
CASES = [(shape, layer, decay) for shape in sorted(SHAPES)
         for layer in range(LAYERS) for decay in DECAYS
         if not shape.startswith("rows") or DECAYS[layer] == decay]


@pytest.mark.parametrize("shape, layer, decay", CASES)
def test_one_layer_moves_one_position_and_no_other_byte(shape, layer, decay):
    rows, groups, per, hd, n = SHAPES[shape]
    stack, x, bm, cm, dt, la = _inputs(rows, groups, per, hd, n,
                                       seed=7 * layer + len(shape))
    if decay == "one":          # exp_ulp(0) is exactly 1: the state is kept
        la = jnp.zeros_like(la)
    elif decay == "zero":       # and exp_ulp(-100) exactly 0: it is forgotten
        la = jnp.full_like(la, -100.0)
    a = exp_ulp(la)
    assert decay == "drawn" or float(a.min()) == float(a.max()) == \
        {"one": 1.0, "zero": 0.0}[decay]
    heads = groups * per
    before = np.asarray(stack)
    wanted_y, wanted = nemotron_h.ssm_step(
        x, bm, cm, dt, la, stack[layer].reshape(rows, groups, per, hd, n))
    got, y = ssm_step.step(
        stack, jnp.int32(layer), a.reshape(rows, heads),
        (dt[..., None] * x).reshape(rows, heads, hd), bm, cm, interpret=True)
    assert got.shape == stack.shape and got.dtype == jnp.float32
    _close(y, wanted_y.reshape(rows, heads, hd))
    _close(got[layer], wanted.reshape(rows, heads, hd, n))
    for other in range(LAYERS):
        if other != layer:      # bit for bit
            np.testing.assert_array_equal(np.asarray(got[other]),
                                          before[other])


def test_a_grid_cell_holds_the_rows_that_divide_the_batch_and_fit():
    group = 16 * 64 * 128 * 4
    assert ssm_step.BLOCK_BYTES == 4 * group
    assert [ssm_step.row_tile(rows, group) for rows in (128, 8, 6, 5, 1)] \
        == [4, 4, 3, 1, 1]
    assert ssm_step.row_tile(8, 100 * group) == 1
    assert ssm_step.whole_tiles(64, 128) and ssm_step.whole_tiles(8, 256)
    assert not ssm_step.whole_tiles(8, 8) and not ssm_step.whole_tiles(4, 128)
