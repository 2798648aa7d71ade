"""`tools/bench_block_ladder.py --tiny`: the ladder's rungs still trace, run
and print on the CPU, so the tool is there when a chip call needs it."""
import json

import pytest

from tools import bench_block_ladder


@pytest.mark.parametrize("layout", ["8x197", "flat"])
def test_the_tiny_ladder_runs_every_rung(layout, capsys):
    bench_block_ladder.main(
        ["--tiny", "--gelus", "jax.nn,layers", "--layouts", layout])
    head, *lines = map(json.loads, capsys.readouterr().out.splitlines())
    assert head["gflop_a_block"] == pytest.approx(
        bench_block_ladder.product_flops(2 * 9, 128, 512) / 1e9)
    assert [(line["rung"], line["gelu"]) for line in lines] == [
        ("bare", None), ("+biases", None), ("+GeLU", "jax.nn"),
        ("+GeLU", "layers"), ("+residuals and norms", "jax.nn"),
        ("+residuals and norms", "layers")]
    for line in lines:
        assert line["layout"] == layout and line["us_a_block"] > 0
        # a share of the v5e's peak is a chip's number: never a CPU's time
        assert "share_of_peak" not in line


def test_an_unknown_gelu_or_layout_is_refused():
    with pytest.raises(KeyError):
        bench_block_ladder.main(["--tiny", "--layouts", "8x197+qkv"])
    with pytest.raises(KeyError):
        bench_block_ladder.main(["--tiny", "--gelus", "relu"])
