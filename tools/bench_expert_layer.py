"""One expert-layer call alone at the sparse cells' decode-step shapes: the
tile loop against the grouped kernel (`parallel/expert.py::topk_ffn_delta`).

The evidence behind `GROUPED_RIDGE`, `grouped_layout` and
`ops/grouped_matmul.py::BLOCK_BYTES`. The tool reads nothing of the kernel
but those names, so the same file run in a checkout of an earlier commit
times that commit's form of the grouped way (PR 50: the parent's two
kernels against the one). For each cell: the real stack of
bfloat16 experts (`[layers, held, F, D]`, random values; in the token's
latent and without a gate matrix where the configuration says so), float32
rows, a random router; one program scans the stack's layers, each layer's rows the
normalised sum of the rows before and their delta, rolled by one lane, so
every call routes anew and a program is `layers x --inner` calls behind
one dispatch. Median
of `--reps` such programs, ms a layer call, and the touched experts' bytes
over that time as a share of the chip's 819 GB/s; and layer 0's delta
against the loop's, the largest gap as a share of the loop's range (the two
ways differ by the order of their float32 sums). Prints one JSON line a cell
and way; before them, where the kernel makes a row's three bfloat16 parts
itself (`grouped_matmul.row_parts`), one line that says whether the parts
a compiled kernel made of 2 M values are `layers._three_parts`' bit for bit
and how many second parts are not zero (a compiler that kept the excess
precision of the first would leave none).

Usage: python tools/bench_expert_layer.py [--cells lfm2,laguna] \
    [--row-tiles 16,32a] [--block-mib 2,4] [--tokens N] [--ops 12] [--tiny]
A row tile with an `a` lays every group on a tile of its own, one without
packs them (`expert.grouped_layout` says which a call takes).
`--tokens` overrides a cell's rows (a span's 4,096, say, with
`--ridge 256` to see the grouped kernel above the ridge). `--tiny` runs the
registry's tiny models with the kernel in interpret mode (a rehearsal on
the CPU: no time of it means anything).
"""
import argparse
import functools
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9

# cell -> (model with the benchmark's cut, its tiny stand-in, rows of a step)
CELLS = {
    "lfm2": ("LiquidAI/LFM2-8B-A1B@12", "pipeedge/test-tiny-lfm2", 128),
    "laguna": ("poolside/Laguna-XS.2@5", "pipeedge/test-tiny-laguna", 32),
    "qwen3-next": ("Qwen/Qwen3-Next-80B-A3B-Instruct@4,e0+256,v75968",
                   "pipeedge/test-tiny-qwen3-next@8,e0+4,v50", 8),
    "keye": ("Kwai-Keye/Keye-VL-2.0-30B-A3B@6", "pipeedge/test-tiny-keye", 8),
    "kimi": ("moonshotai/Kimi-K2-Instruct@5,e0+12,v20480",
             "pipeedge/test-tiny-kimi@3,e0+2,v50", 32),
    "nemotron": (
        "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16@11,e0+128,v32768",
        "pipeedge/test-tiny-nemotron-h@8,e0+2,v50", 128),
}


def _expert_layers(cfg) -> int:
    """Routed layers of the cell's cut: a kind of their own where a block
    is one sublayer (nemotron_h), else every block past the dense ones."""
    if "experts" in cfg.layer_types:
        return cfg.layer_types[:cfg.num_hidden_layers].count("experts")
    return cfg.num_hidden_layers - cfg.first_k_dense


def _layer_params(cfg, layers, key):
    """Router and stacked experts of `layers` routed layers, on the
    device, bfloat16 as the cells store them."""
    import jax
    import jax.numpy as jnp
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    wide = cfg.moe_latent_size or d         # what the routed experts read
    held = cfg.held_experts[1] if cfg.held_experts else cfg.n_experts
    keys = jax.random.split(key, 6)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def one_layer(k, rows, cols):
        return (jax.random.normal(k, (held, rows, cols), jnp.bfloat16)
                * jnp.bfloat16(cols ** -0.5))

    def stack(k, rows, cols):
        # a layer at a time: the draw's temporaries are several times its
        # result, and nemotron's leaf is 3.5 GB
        return jnp.stack([one_layer(layer_key, rows, cols)
                          for layer_key in jax.random.split(k, layers)])
    router = {"w": jax.random.normal(keys[3], (d, cfg.n_experts),
                                     jnp.float32) * d ** -0.5}
    if cfg.router == "sigmoid":
        router["bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    params = {"router": router,
              "experts": {"up": stack(keys[1], f, wide),
                          "down": stack(keys[2], wide, f)}}
    if cfg.expert_act == "silu":
        params["experts"]["gate"] = stack(keys[0], f, wide)
    if cfg.moe_latent_size:
        params["latent"] = {"down": one_layer(keys[4], wide, d)[0],
                            "up": one_layer(keys[5], d, wide)[0]}
    return params


def _parts_on_this_device(grouped_matmul):
    """Whether `row_parts` inside a compiled kernel gives `_three_parts`'
    values, and how many of the second parts are not zero."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from pipeedge_tpu.models.layers import _three_parts
    rows, width = 16, 1024

    def kernel(x_ref, o_ref):
        o_ref[...] = grouped_matmul.row_parts(x_ref[...], jnp.bfloat16)[1]
    x = jax.random.normal(jax.random.PRNGKey(50), (128 * rows, width),
                          jnp.float32)
    got = pl.pallas_call(
        kernel, grid=(128,),
        in_specs=[pl.BlockSpec((rows, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((3 * rows, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((128 * 3 * rows, width), jnp.bfloat16),
        interpret=jax.default_backend() != "tpu")(x)
    got = got.reshape(128, 3, rows, width).swapaxes(0, 1).reshape(
        3, 128 * rows, width)
    wanted = jax.jit(lambda y: _three_parts(y, jnp.bfloat16))(x)
    return {"parts_equal": bool(jnp.all(got == wanted)),
            "second_parts_nonzero": int(jnp.sum(got[1] != 0)),
            "third_parts_nonzero": int(jnp.sum(got[2] != 0)),
            "values": int(x.size)}


def _program(cfg, layers, inner):
    import jax
    import jax.numpy as jnp
    from pipeedge_tpu.parallel import expert

    def run(params, x):
        def one(carry, layer):
            x, counts = carry
            delta, stats = expert.topk_ffn_delta(params, x, cfg, layer=layer)
            # rolled along the hidden axis: the one router sees other rows
            # in every call, also where a share's delta left a row as it was
            x = jnp.roll(x + delta, 1, axis=-1)
            x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))
            return (x, counts + stats), None
        start = (x, jnp.zeros((len(expert.MOE_STATS),), jnp.float32))
        (x, counts), _ = jax.lax.scan(
            one, start, jnp.tile(jnp.arange(layers), inner))
        return x, counts
    return jax.jit(run)


def _device_ops(run, params, x, calls, top):
    """One more run under the profiler: the `top` longest operations of the
    device, microseconds of self time a layer call, by the names
    `benchmark/xplane.py` gives them."""
    import tempfile
    import jax
    from benchmark import xplane
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready(run(params, x))
        device_ops, _ = xplane.read(xplane.find_trace(trace_dir))
    if not device_ops:      # the CPU's rehearsal: no device plane
        return {}
    ops, = device_ops.values()
    times = {}
    for name, seconds in xplane.self_times(ops).items():
        name = name.partition("/")[2]
        times[name] = times.get(name, 0.0) + seconds
    longest = sorted(times.items(), key=lambda item: -item[1])[:top]
    return {"us_a_call_all": round(sum(times.values()) / calls * 1e6, 1),
            "us_a_call": {name: round(seconds / calls * 1e6, 1)
                          for name, seconds in longest}}


def _first_layer(cfg):
    import jax
    from pipeedge_tpu.parallel import expert
    return jax.jit(lambda params, x: expert.topk_ffn_delta(
        params, x, cfg, layer=0)[0])


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--cells", default=",".join(CELLS))
    p.add_argument("--row-tiles", default="",
                   help="grouped row tiles to try beside the rule's")
    p.add_argument("--block-mib", default="",
                   help="matrix block sizes to try beside BLOCK_BYTES")
    p.add_argument("--tokens", type=int, default=0)
    p.add_argument("--ridge", type=int, default=0)
    p.add_argument("--inner", type=int, default=4)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ops", type=int, default=0,
                   help="trace a run of each way and list this many of its "
                        "longest device operations, us a layer call")
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.ops import grouped_matmul
    from pipeedge_tpu.parallel import expert

    device = jax.devices()[0]
    print(json.dumps({"device": {"platform": device.platform,
                                 "kind": device.device_kind}}), flush=True)
    if args.ridge:
        expert.GROUPED_RIDGE = args.ridge
    if hasattr(grouped_matmul, "row_parts"):
        print(json.dumps(_parts_on_this_device(grouped_matmul)), flush=True)
    rule, block = expert.grouped_layout, grouped_matmul.BLOCK_BYTES
    ways = [("loop", None, block), ("grouped", None, block)]
    ways += [("grouped", (int(rows.rstrip("a")), rows.endswith("a")), block)
             for rows in args.row_tiles.split(",") if rows]
    ways += [("grouped", None, int(float(mib) * 2 ** 20))
             for mib in args.block_mib.split(",") if mib]

    for cell in args.cells.split(","):
        name, tiny, tokens = CELLS[cell]
        entry = registry.get_model_entry(tiny if args.tiny else name)
        cfg = entry.config
        tokens = args.tokens or tokens
        layers = _expert_layers(cfg)
        key = jax.random.PRNGKey(args.seed)
        params = _layer_params(cfg, layers, key)
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (tokens, 1, cfg.hidden_size), jnp.float32)
        expert_bytes = sum(math.prod(leaf.shape[2:]) * leaf.dtype.itemsize
                           for leaf in params["experts"].values())
        calls = layers * args.inner
        tile = expert.expert_tile(tokens, cfg.num_experts_per_tok,
                                  cfg.n_experts)
        loops = None
        for way, rows, block_bytes in ways:
            mode = None if way == "loop" \
                else "interpret" if args.tiny else "mosaic"
            expert._grouped_mode = lambda mode=mode: mode
            expert.grouped_layout = rule if rows is None \
                else (lambda tile, rows=rows: rows)
            grouped_matmul.BLOCK_BYTES = block_bytes
            run = _program(cfg, layers, args.inner)
            _, counts = jax.block_until_ready(run(params, x))
            delta = _first_layer(cfg)(params, x)
            if loops is None:
                loops = delta
            gap = float(jnp.max(jnp.abs(delta - loops))
                        / (jnp.max(loops) - jnp.min(loops)))
            times = []
            for _ in range(args.reps):
                tik = time.perf_counter()
                jax.block_until_ready(run(params, x))
                times.append((time.perf_counter() - tik) / calls)
            ms = statistics.median(times) * 1e3
            touched = float(counts[2]) / calls
            ops = _device_ops(run, params, x, calls, args.ops) \
                if args.ops and way == "grouped" else {}
            print(json.dumps({
                "cell": cell, "way": way, "tokens": tokens, "tile": tile,
                "row_tile": (rows or rule(tile)) if way == "grouped" else None,
                "block_mib": block_bytes / 2 ** 20, "layers": layers,
                "ms_a_call": round(ms, 4),
                "experts_touched_a_call": round(touched, 2),
                "rows_computed_a_call": float(counts[1]) / calls,
                "grouped_calls": float(counts[3]),
                "gap_share_of_range": gap,
                "hbm_share_pct": round(100 * touched * expert_bytes
                                       / (ms * 1e-3) / HBM_BYTES_PER_S, 2),
                **ops}), flush=True)
        del params


if __name__ == "__main__":
    main()
