"""One Mamba-2 layer's state step alone at the shapes of the cells that
run it: the jnp step behind a `dynamic_update_slice` of the donated stack
(`models/mamba2.py::ssm_step`, what every backend without Mosaic runs)
against the in-place kernel (`ops/ssm_step.py`).

The evidence behind `ops/ssm_step.py::BLOCK_BYTES`. A stack of layers' state
`[L, rows, H, P, N]` float32 whose heads share B and C in `G` groups
(`--stacks`, `L,rows,H,P,N/G` each: `nemotron3-super.reason-batch`'s five
layers of 128 heads in 8 groups at 128 rows, 2.68 GB, a grid cell 4 rows of
a group; `granite4-h-micro.summary-batch`'s 36 layers of 64 heads in ONE
group at 64 rows, 4.83 GB, a grid cell one row's whole state) is donated to
one program that moves every layer `--inner` positions on, each layer's `y`
folded into the next call's `x` so that the calls stay in order; median of
`--reps` programs, ms a layer call, and the state's bytes read once and
written once over that time as a share of the chip's 819 GB/s. Then one
position of both ways from the same stack: the largest gap of `y` and of
the state as a share of their range (the ways differ by the order of the
sum over N). Prints one JSON line a stack and way.

Usage: python tools/bench_ssm_step.py [--stacks 5,128,128,64,128/8;36,64,64,64,128/1]
    [--block-mib 0.5,1,2,4] [--tiny]
`--tiny` runs a small stack with the kernel in interpret mode (a rehearsal
on the CPU: no time of it means anything).
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9


def _program(way, layers, inner, interpret):
    """(stack, x, bm, cm, dt, la) -> (stack, y): every layer `inner`
    positions on, through `way` ("jnp" or "kernel")."""
    import jax
    import jax.numpy as jnp
    from pipeedge_tpu.models import mamba2
    from pipeedge_tpu.models.decoder import exp_ulp
    from pipeedge_tpu.ops import ssm_step

    def one(stack, layer, x, bm, cm, dt, la):
        b, g, per, hd = x.shape
        if way == "kernel":
            stack, y = ssm_step.step(
                stack, layer, exp_ulp(la).reshape(b, -1),
                (dt[..., None] * x).reshape(b, g * per, hd), bm, cm,
                interpret=interpret)
            return stack, y.reshape(x.shape)
        y, new = mamba2.ssm_step(
            x, bm, cm, dt, la, stack[layer].reshape(x.shape + bm.shape[-1:]))
        return jax.lax.dynamic_update_slice(
            stack, new.reshape((1,) + stack.shape[1:]),
            (layer, 0, 0, 0, 0)), y

    def run(stack, x, bm, cm, dt, la):
        y = jnp.zeros_like(x)
        for _ in range(inner):
            for layer in range(layers):
                stack, y = one(stack, layer, x + 1e-3 * y, bm, cm, dt, la)
        return stack, y

    return jax.jit(run, donate_argnums=(0,))


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--stacks", default="5,128,128,64,128/8;36,64,64,64,128/1",
                   help="the stacks to time, `L,rows,H,P,N/G` each, `;` "
                        "between them (default: the two cells')")
    p.add_argument("--block-mib", default="",
                   help="`ssm_step.BLOCK_BYTES` to try, in MiB (default: "
                        "the module's)")
    p.add_argument("--inner", type=int, default=4)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args()
    import jax
    from pipeedge_tpu.ops import ssm_step
    print("devices:", json.dumps({
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": jax.device_count()}))
    blocks = [float(b) for b in args.block_mib.split(",") if b] or [
        ssm_step.BLOCK_BYTES / 2 ** 20]
    for spec in ("2,2,4,8,8/2;2,2,4,8,8/1" if args.tiny
                 else args.stacks).split(";"):
        shape, _, groups = spec.partition("/")
        layers, rows, heads, hd, n = (int(size) for size in shape.split(","))
        _time_stack(args, blocks, layers, rows, int(groups or 1), heads, hd,
                    n)


def _time_stack(args, blocks, layers, rows, groups, heads, hd, n):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from pipeedge_tpu.ops import ssm_step
    per = heads // groups
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    x = jax.random.normal(keys[0], (rows, groups, per, hd), jnp.float32)
    bm = jax.random.normal(keys[1], (rows, groups, n), jnp.float32)
    cm = jax.random.normal(keys[2], (rows, groups, n), jnp.float32)
    dt = jax.random.uniform(keys[3], (rows, groups, per), jnp.float32,
                            1e-3, 0.1)
    la = -dt * jnp.linspace(1.0, 16.0, groups * per).reshape(groups, per)
    shape = (layers, rows, groups * per, hd, n)
    layer_bytes = int(np.prod(shape[1:])) * 4

    def fresh():
        return jax.random.normal(keys[4], shape, jnp.float32)

    ways = [("jnp", None)] + [("kernel", mib) for mib in blocks]
    first = {}
    for way, mib in ways:
        if mib is not None:
            ssm_step.BLOCK_BYTES = int(mib * 2 ** 20)
            ssm_step.step.clear_cache()
        once = _program(way, 1, 1, args.tiny)
        stack, y = once(fresh(), x, bm, cm, dt, la)
        first[way] = (np.asarray(stack[0]), np.asarray(y))
        del stack
        run = _program(way, layers, args.inner, args.tiny)
        stack = fresh()
        stack, y = run(stack, x, bm, cm, dt, la)      # compiles
        jax.block_until_ready(y)
        times = []
        for _ in range(args.reps):
            start = time.perf_counter()
            stack, y = run(stack, x, bm, cm, dt, la)
            jax.block_until_ready((stack, y))
            times.append(time.perf_counter() - start)
        del stack
        call_s = statistics.median(times) / (layers * args.inner)
        gaps = {name: float(np.abs(got - wanted).max()
                            / (wanted.max() - wanted.min()))
                for name, got, wanted in zip(
                    ("state", "y"), first[way], first["jnp"])}
        print(json.dumps({
            "stack": list(shape), "groups": groups,
            "way": way, "block_mib": mib, "rows": rows,
            "rows_a_cell": None if mib is None else ssm_step.row_tile(
                rows, per * hd * n * 4),
            "ms_a_layer_call": round(1e3 * call_s, 4),
            "state_bytes_moved": 2 * layer_bytes,
            "hbm_share": round(2 * layer_bytes / call_s / HBM_BYTES_PER_S, 4),
            "gap_to_jnp": gaps}), flush=True)


if __name__ == "__main__":
    main()
