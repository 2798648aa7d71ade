"""A ViT-L block's six weight products alone, rung by rung up to the block
less its attention core: where the distance between the bare products and
the block lies (PERF.md section 6, PRs 60 and 61; ROADMAP S7).

q, k, v, out, up, down at the model's widths (39.66 GFLOP at 8 x 197 rows,
201.3 us at the v5e's bf16 peak) chained over six blocks of stacked weights,
`q + k + v` standing for the core, 16 passes in one program, wall clock, the
best of five. The rungs: the bare products (float32 accumulation, bfloat16
out); + biases; + the GeLU behind the up product, in each of `--gelus`
(`jax.nn`: `jax.nn.gelu(approximate=False)`, XLA's `erfc`, every program's
until PR 61; `layers`: `models/layers.py::gelu`, the shipped form; `tanh`:
what `PIPEEDGE_FAST_NUMERICS` buys); + residuals and the norms' float32
statistics, over each GeLU. `--layouts`: rows as `[8, 197, D]` or flattened
to `[1576, D]`. A rung timed here ranks candidates; only the cells say what
a change gives (PR 60: a piece timed alone cannot rank a kernel against
XLA). One JSON line a rung, with its share of the v5e's peak where the
device is a TPU.

Usage: python tools/bench_block_ladder.py [--gelus jax.nn,layers,tanh]
    [--layouts 8x197,flat] [--tiny]
`--tiny` is a rehearsal on the CPU (`tests/test_bench_block_ladder.py`): no
time of it means anything.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCKS, PASSES = 6, 16
PEAK_FLOPS = 197e12
RUNGS = ("bare", "+biases", "+GeLU", "+residuals and norms")


def product_flops(rows, d, ff):
    return 2 * rows * (4 * d * d + 2 * d * ff)


def block(rung, gelu):
    """One block's products up to `rung` (an index of RUNGS)."""
    import jax
    import jax.numpy as jnp
    bias, act, rest = rung >= 1, rung >= 2, rung >= 3

    def dot(x, w, b):
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return (y + b if bias else y).astype(x.dtype)

    def norm(x, p):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        return ((xf - mean) * jax.lax.rsqrt(var + 1e-6) * p[0]
                + p[1]).astype(x.dtype)

    def run(x, w):
        y = norm(x, w["ln1"]) if rest else x
        core = dot(y, w["q"], w["q_b"]) + dot(y, w["k"], w["k_b"]) \
            + dot(y, w["v"], w["v_b"])
        out = dot(core, w["o"], w["o_b"])
        x = out + x if rest else out
        y = norm(x, w["ln2"]) if rest else x
        up = dot(y, w["up"], w["up_b"])
        if act:
            up = gelu(up)
        down = dot(up, w["down"], w["down_b"])
        return down + x if rest else down
    return run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--gelus", default="jax.nn,layers,tanh")
    ap.add_argument("--layouts", default="8x197")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pipeedge_tpu.models import layers
    gelus = {"jax.nn": lambda v: jax.nn.gelu(v, approximate=False),
             "layers": layers.gelu,
             "tanh": lambda v: jax.nn.gelu(v, approximate=True)}
    b, s, d, ff = (2, 9, 128, 512) if args.tiny else (8, 197, 1024, 4096)
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16

    def mat(i, o):
        return jnp.asarray(rng.normal(size=(BLOCKS, i, o)) * i ** -0.5, dt)

    def vec(o):
        return jnp.asarray(rng.normal(size=(BLOCKS, o)) * 0.02, dt)
    w = {n: mat(d, d) for n in "qkvo"}
    w.update({n + "_b": vec(d) for n in "qkvo"})
    w.update(up=mat(d, ff), up_b=vec(ff), down=mat(ff, d), down_b=vec(d),
             ln1=jnp.ones((BLOCKS, 2, d), jnp.float32),
             ln2=jnp.ones((BLOCKS, 2, d), jnp.float32))
    flops = product_flops(b * s, d, ff)
    on_the_chip = jax.devices()[0].platform == "tpu"
    print(json.dumps({"device": str(jax.devices()[0]),
                      "gflop_a_block": flops / 1e9,
                      "us_at_peak": flops / PEAK_FLOPS * 1e6}), flush=True)
    for layout in args.layouts.split(","):
        x = jnp.asarray(rng.normal(
            size={"flat": (b * s, d), "8x197": (b, s, d)}[layout]), dt)
        for rung, name in enumerate(RUNGS):
            for form in args.gelus.split(",") if rung >= 2 else (None,):
                run = block(rung, gelus[form] if form else None)

                @jax.jit
                def chain(x, w, run=run):
                    def once(_, x):
                        return jax.lax.scan(
                            lambda c, wi: (run(c, wi), None), x, w)[0]
                    return jax.lax.fori_loop(0, PASSES, once, x)
                jax.block_until_ready(chain(x, w))
                best = float("inf")
                for _ in range(5):
                    tik = time.perf_counter()
                    for _ in range(4):
                        out = chain(x, w)
                    jax.block_until_ready(out)
                    best = min(best, (time.perf_counter() - tik) / 4)
                us = best / (PASSES * BLOCKS) * 1e6
                line = {"rung": name, "gelu": form, "layout": layout,
                        "us_a_block": round(us, 2)}
                if on_the_chip and not args.tiny:
                    line["share_of_peak"] = round(
                        flops / PEAK_FLOPS / (us * 1e-6), 4)
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
