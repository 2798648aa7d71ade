"""One expert-layer call alone at the sparse cells' decode-step shapes: the
tile loop against the grouped kernel (`parallel/expert.py::topk_ffn_delta`).

The evidence behind `GROUPED_RIDGE`, `grouped_layout` and
`ops/grouped_matmul.py::BLOCK_BYTES`. For each cell: the real stack of
bfloat16 experts (`[layers, held, F, D]`, random values), float32 rows, a
random router; one program scans the stack's layers, each layer's rows the
normalised sum of the rows before and their delta, rolled by one lane, so
every call routes anew and a program is `layers x --inner` calls behind
one dispatch. Median
of `--reps` such programs, ms a layer call, and the touched experts' bytes
over that time as a share of the chip's 819 GB/s; and layer 0's delta
against the loop's, the largest gap as a share of the loop's range (the two
ways differ by the order of their float32 sums). Prints one JSON line a cell
and way.

Usage: python tools/bench_expert_layer.py [--cells lfm2,laguna] \
    [--row-tiles 16,32a] [--block-mib 2,4] [--tokens N] [--tiny]
A row tile with an `a` lays every group on a tile of its own, one without
packs them (`expert.grouped_layout` says which a call takes).
`--tokens` overrides a cell's rows (a span's 4,096, say, with
`--ridge 256` to see the grouped kernel above the ridge). `--tiny` runs the
registry's tiny models with the kernel in interpret mode (a rehearsal on
the CPU: no time of it means anything).
"""
import argparse
import json
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
}


def _layer_params(cfg, layers, key):
    """Router and stacked experts of `layers` routed layers, on the
    device, bfloat16 as the cells store them."""
    import jax
    import jax.numpy as jnp
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    held = cfg.held_experts[1] if cfg.held_experts else cfg.n_experts
    keys = jax.random.split(key, 4)

    def stack(k, rows, cols):
        return (jax.random.normal(k, (layers, held, rows, cols), jnp.bfloat16)
                * jnp.bfloat16(cols ** -0.5))
    router = {"w": jax.random.normal(keys[3], (d, cfg.n_experts),
                                     jnp.float32) * d ** -0.5}
    if cfg.router == "sigmoid":
        router["bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    return {"router": router,
            "experts": {"gate": stack(keys[0], f, d), "up": stack(keys[1], f, d),
                        "down": stack(keys[2], d, f)}}


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
        layers = cfg.num_hidden_layers - cfg.first_k_dense
        key = jax.random.PRNGKey(args.seed)
        params = _layer_params(cfg, layers, key)
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (tokens, 1, cfg.hidden_size), jnp.float32)
        expert_bytes = 3 * cfg.hidden_size * cfg.moe_intermediate_size * 2
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
            }), flush=True)
        del params


if __name__ == "__main__":
    main()
