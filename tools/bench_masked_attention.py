"""One KV group's masked attention alone at the span shapes of
`keye-vl2.long-batch`, `minicpm-sala.longctx-batch` and
`qwen3-next.longdoc-batch`: the XLA einsums against the streaming kernel
(`models/decoder.py::attend_masked`).

The evidence behind `decoder.FUSED_ROWS` and `ops/masked_attention.py`'s
`QUERY_TILE` / `KEY_BLOCK`. For each shape: float32 queries of one query
chunk `[B, Q, r, 128]`, a cached window `[B, width, 128]` whose positions at
or past `live` are dead (the ladder's share) and the span's own rows under a
causal mask; keye's mask keeps a random `topk / live` of the live keys a
query, SALA's random blocks of 64. qwen3-next's shapes (heads of 256, the
mask causal and nothing else) are a whole span of 1,024 queries through the
family's own `qwen3_next.attend` with one KV group: the einsums in the
query chunks `decoder.SCORE_BYTES` gives the cell, the kernel in one call.
One program runs `--inner` calls, each
call's context the next one's queries, behind one dispatch; median of
`--reps` programs, ms a call; the six-pass products over the LIVE keys (a
causal shape: over the live PAIRS, a query's keys at or before it) as a
share of the chip's 197 TFLOP/s; the kernel's context against the einsums'
(largest gap over the einsums' range) and both against float64 on the host
for the first row's first queries. Prints one JSON line a shape and way.

Usage: python tools/bench_masked_attention.py [--shapes keye-wide,sala-wide]
    [--key-blocks 256,1024] [--query-tiles 64] [--tiny]
`--tiny` runs small shapes with the kernel in interpret mode (a rehearsal on
the CPU: no time of it means anything).
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_FLOPS = 197e12
PASSES = 6

# shape -> (rows, query heads a KV group, queries a chunk, window, live
# positions of it, own rows, what a query keeps: ("top", k), ("blocks",
# share) or ("causal", a head's lanes)). keye: 8 rows, chunks of
# `decoder.query_chunk(512, ...)`; SALA: 2 rows, spans of 1,024; the widest
# and the narrowest window each attends; qwen3-next: 8 rows, a span's 1,024
# queries, the first span, the second, a middle window and the widest
SHAPES = {
    "keye-wide": (8, 8, 64, 16384, 15360, 512, ("top", 2048)),
    "keye-mid": (8, 8, 256, 7168, 6656, 512, ("top", 2048)),
    "keye-narrow": (8, 8, 256, 512, 512, 512, ("top", 2048)),
    "keye-own": (8, 8, 512, 0, 0, 512, ("top", 2048)),
    "sala-wide": (2, 16, 32, 65536, 63488, 1024, ("blocks", 97 / 1008)),
    "sala-mid": (2, 16, 128, 28672, 27648, 1024, ("blocks", 97 / 448)),
    "sala-narrow": (2, 16, 1024, 1024, 1024, 1024, ("blocks", 1.0)),
    "sala-own": (2, 16, 1024, 0, 0, 1024, ("blocks", 1.0)),
    "qwen-wide": (8, 8, 1024, 32768, 31744, 1024, ("causal", 256)),
    "qwen-mid": (8, 8, 1024, 16384, 15360, 1024, ("causal", 256)),
    "qwen-narrow": (8, 8, 1024, 1024, 1024, 1024, ("causal", 256)),
    "qwen-own": (8, 8, 1024, 0, 0, 1024, ("causal", 256)),
}
TINY = {
    "keye-wide": (2, 8, 32, 512, 384, 128, ("top", 64)),
    "sala-wide": (1, 16, 16, 1024, 896, 128, ("blocks", 0.2)),
    "qwen-wide": (1, 8, 128, 1024, 640, 128, ("causal", 256)),
}


def _inputs(shape, seed):
    import jax
    import jax.numpy as jnp
    b, r, n_q, width, live, own, (kind, amount) = shape
    hd = amount if kind == "causal" else 128
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(keys[0], (b, n_q, r, hd), jnp.float32)
    ks, vs, keeps = [], [], []
    at = own - n_q + jnp.arange(n_q)        # the chunk is the span's last
    for i, n in enumerate((width, own)):
        if not n:
            continue
        ks.append(jax.random.normal(keys[1 + i], (b, n, hd), jnp.float32))
        vs.append(jax.random.normal(keys[3 + i], (b, n, hd), jnp.float32))
        alive = (jnp.arange(n) < live)[None, None] if i == 0 and width \
            else (jnp.arange(n)[None, :] <= at[:, None])[None]
        if kind == "causal":    # one mask for every row, as the family's
            keeps.append(jnp.broadcast_to(alive, (1, n_q, n)))
            continue
        draw = jax.random.uniform(keys[5 + i], (b, n_q, n))
        if kind == "top":
            chosen = draw < min(1.0, amount / max(live + own, 1))
        else:
            chosen = jnp.repeat(draw[..., ::64] < amount, 64, axis=-1)
        keeps.append(chosen & alive)
    # every query keeps itself, as in the families
    keeps[-1] = keeps[-1] | (jnp.arange(own)[None, :] == at[:, None])[None]
    return q, ks, vs, keeps


def _float64(q, ks, vs, keeps, n_q):
    import numpy as np
    q = np.asarray(q[0, :n_q], np.float64)                      # [Q, r, Dh]
    scores = [np.where(np.asarray(keep[0, :n_q])[:, None], np.einsum(
        "qrd,kd->qrk", q, np.asarray(k[0], np.float64))
        * q.shape[-1] ** -0.5, -np.inf) for k, keep in zip(ks, keeps)]
    top = np.max(np.concatenate(scores, -1), -1, keepdims=True)
    probs = [np.exp(sc - top) for sc in scores]
    total = sum(pr.sum(-1) for pr in probs)[..., None]
    return sum(np.einsum("qrk,kd->qrd", pr, np.asarray(v[0], np.float64))
               for pr, v in zip(probs, vs)) / total


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--shapes", default=",".join(SHAPES))
    p.add_argument("--key-blocks", default="",
                   help="KEY_BLOCKs to try beside the module's")
    p.add_argument("--query-tiles", default="",
                   help="QUERY_TILEs to try beside the module's")
    p.add_argument("--inner", type=int, default=4)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pipeedge_tpu.models import decoder, qwen3_next
    from pipeedge_tpu.ops import masked_attention

    device = jax.devices()[0]
    if not args.tiny and device.platform != "tpu":
        sys.exit("bench_masked_attention: no TPU here (--tiny rehearses on "
                 "the CPU)")
    print(json.dumps({"device": {"platform": device.platform,
                                 "kind": device.device_kind}}), flush=True)
    fused = "interpret" if args.tiny else "mosaic"
    shapes = TINY if args.tiny else SHAPES
    ways = [("einsum", None, None), ("kernel", None, None)]
    ways += [("kernel", int(bk), None)
             for bk in args.key_blocks.split(",") if bk]
    ways += [("kernel", None, int(bq))
             for bq in args.query_tiles.split(",") if bq]
    defaults = masked_attention.KEY_BLOCK, masked_attention.QUERY_TILE

    for name in args.shapes.split(","):
        if name not in shapes:
            continue
        shape = shapes[name]
        q, ks, vs, keeps = _inputs(shape, args.seed)
        b, r, n_q = shape[:3]
        hd, causal = q.shape[-1], shape[6][0] == "causal"
        live = sum(int(jnp.sum(jnp.any(keep, axis=(0, 1)))) for keep in keeps)
        pairs = b * sum(int(jnp.sum(keep)) for keep in keeps) if causal \
            else b * n_q * live
        flops = 4 * r * pairs * hd * PASSES
        exact = _float64(q, ks, vs, keeps, min(n_q, 8))
        if causal:
            # the family's own call, one KV group: it builds the masks from
            # `pos` a chunk and chunks the einsums as the cell's program does
            def attend(q, pos, ks, vs, keeps):
                parts = [((k,), (v,), False)
                         for k, v in zip(ks[:-1], vs[:-1])] \
                    + [((ks[-1],), (vs[-1],), True)]
                return qwen3_next.attend(q, parts, pos)[0].reshape(q.shape)
        else:
            def attend(q, pos, ks, vs, keeps):
                return decoder.attend_masked(q, ks, vs, keeps)[0]
        # operands as arguments: closed over they would be constants of the
        # program, 0.8 GB of them at the widest shape
        operands = (jnp.int32(shape[4]), ks, vs, keeps)
        first = None
        for way, bk, bq in ways:
            masked_attention.KEY_BLOCK = bk or defaults[0]
            masked_attention.QUERY_TILE = bq or defaults[1]
            jax.clear_caches()      # `attend` is jitted: trace the new tiles
            decoder._fused_mode = lambda way=way: \
                fused if way == "kernel" else None

            def run(q, *operands):
                def one(_, q):
                    ctx = attend(q, *operands)
                    return ctx * jax.lax.rsqrt(
                        jnp.mean(ctx * ctx, axis=-1, keepdims=True))
                return jax.lax.fori_loop(0, args.inner, one, q)

            program = jax.jit(run)
            ctx = np.asarray(jax.jit(attend)(q, *operands))
            jax.block_until_ready(program(q, *operands))
            times = []
            for _ in range(args.reps):
                tik = time.perf_counter()
                jax.block_until_ready(program(q, *operands))
                times.append((time.perf_counter() - tik) / args.inner * 1e3)
            ms = statistics.median(times)
            line = {"shape": name, "way": way, "rows": b, "heads": r,
                    "queries": n_q, "head_dim": hd,
                    "keys": [int(k.shape[1]) for k in ks],
                    "live_keys": live, "live_pairs": pairs,
                    "key_block": masked_attention.KEY_BLOCK
                    if way == "kernel" else None,
                    "query_tile": masked_attention.QUERY_TILE
                    if way == "kernel" else None,
                    "ms_a_call": round(ms, 4),
                    "six_pass_peak_share": round(
                        flops / PEAK_FLOPS / (ms * 1e-3), 4),
                    "gap_to_float64_share_of_range": float(
                        np.max(np.abs(ctx[0, :exact.shape[0]] - exact))
                        / np.ptp(exact))}
            if first is None:
                first = ctx
            else:
                line["gap_to_einsum_share_of_range"] = float(
                    np.max(np.abs(ctx - first)) / np.ptp(first))
            print(json.dumps(line), flush=True)
    masked_attention.KEY_BLOCK, masked_attention.QUERY_TILE = defaults


if __name__ == "__main__":
    main()
