"""Isolated int8 decode-attention microbench: XLA vs kernel v1 vs v2.

The decode-step attend over an int8 KV cache is a per-(batch, head)
matvec — no MXU mapping fills the array (M=1 queries), so the op is
HBM-bandwidth-bound and the only lever is bytes moved. The XLA path
dequantizes the window to a bf16/f32 copy before attending (int8 read +
fp write + fp read); the Pallas kernels read int8 once and dequantize
in VMEM. Round 4 measured kernel v1 (per-cell grid) at parity-to-slower
(docs/DECODE.md honest negative); round 5 adds v2 (batch-as-sublane:
grid over KV blocks, all cells per instance — ops/decode_attention.py).

This harness times all three routes interleaved (chained reps, one
scalar fence) at decode-dominant shapes, and calibrates the chip's effective HBM bandwidth with a big
jnp.copy so each route's bytes/roofline is explicit in the record.
Prints ONE JSON line.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-b", "--batch", default=16, type=int)
    p.add_argument("--heads", default=16, type=int)
    p.add_argument("--head-dim", default=64, type=int)
    p.add_argument("--widths", default="256,1024",
                   help="attend window widths; 1024 is the production "
                        "VMEM-cap regime (4096 busts the v1 kernel's "
                        "scoped-vmem stack on v5e — measured, capped)")
    p.add_argument("-t", "--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--chain", default=16, type=int)
    p.add_argument("--rounds", default=3, type=int)
    args = p.parse_args()

    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pipeedge_tpu.ops.decode_attention import (
        int8_decode_attention, int8_decode_attention_supported)
    from pipeedge_tpu.models import stage_cache

    b, h, d = args.batch, args.heads, args.head_dim
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    interpret = not int8_decode_attention_supported()
    rng = np.random.default_rng(0)

    # effective HBM bandwidth via the PAIRED-DELTA estimator: time a
    # chain of N and of 2N dependent copies and divide the difference —
    # the fixed dispatch + readback cost cancels, which a single fenced
    # chain cannot achieve at these op sizes
    big = jax.device_put(jnp.asarray(
        rng.normal(size=(64 << 20) // 4), jnp.float32))
    cp = jax.jit(lambda x: x * jnp.float32(1.000001))
    float(jnp.sum(cp(big)))               # compile + warm

    def chain_copies(k):
        tik = time.monotonic()
        y = big
        for _ in range(k):
            y = cp(y)
        float(jnp.sum(y))
        return time.monotonic() - tik

    # long chains: each leg must dwarf the host's timing jitter or the
    # delta can go negative
    n_bw = 64
    deltas = [chain_copies(2 * n_bw) - chain_copies(n_bw)
              for _ in range(3)]
    med = statistics.median(deltas)
    bw = 2 * n_bw * big.nbytes / med if med > 0 else None

    results = {}
    for width in (int(w) for w in args.widths.split(",")):
        pos = width - 2
        kq = jnp.asarray(rng.integers(-128, 127, size=(b, width, h, d)),
                         jnp.int8)
        vq = jnp.asarray(rng.integers(-128, 127, size=(b, width, h, d)),
                         jnp.int8)
        ks = jnp.asarray(rng.random(size=(b, width, h)) * 0.02, jnp.float32)
        kz = jnp.asarray(rng.random(size=(b, width, h)) - 0.5, jnp.float32)
        vs, vz = ks + 0.001, kz * 0.5
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), dtype)
        k_new = jnp.asarray(rng.normal(size=(b, 1, h, d)), dtype)
        v_new = jnp.asarray(rng.normal(size=(b, 1, h, d)), dtype)

        # cache tensors enter as ARGUMENTS (a closure would bake the
        # multi-MB int8 windows into the HLO as constants)
        operands = (kq, ks, kz, vq, vs, vz, k_new, v_new)

        def xla_route(q, pos, kq, ks, kz, vq, vs, vz, k_new, v_new):
            # the production XLA path's math: dequantize window, fresh
            # row substitution, masked attend (stage_cache.attend)
            k = stage_cache.dequantize_rows(kq, ks, kz, dtype)
            v = stage_cache.dequantize_rows(vq, vs, vz, dtype)
            k = jax.lax.dynamic_update_slice(k, k_new, (0, pos, 0, 0))
            v = jax.lax.dynamic_update_slice(v, v_new, (0, pos, 0, 0))
            keep = (jnp.arange(width) <= pos)[None, :]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores / jnp.sqrt(jnp.float32(d))
            scores = jnp.where(keep[:, None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                              preferred_element_type=jnp.float32) \
                .astype(dtype).reshape(b, 1, h * d)

        def kernel_route(variant):
            def run(q, pos, *t):
                return jnp.sum(int8_decode_attention(
                    q, *t, pos, interpret=interpret,
                    variant=variant).astype(jnp.float32))

            return jax.jit(run)

        routes = {
            "xla": jax.jit(lambda q, pos, *t: jnp.sum(
                xla_route(q, pos, *t).astype(jnp.float32))),
            "kernel_v1": kernel_route(1),
            "kernel_v2": kernel_route(2),
        }
        for fn in routes.values():
            float(fn(q, pos, *operands))  # compile + warm (incl. fence)
        def timed_chain(fn, k):
            tik = time.monotonic()
            out = None
            for _ in range(k):
                out = fn(q, pos, *operands)
            float(out)
            return time.monotonic() - tik

        times = {k: [] for k in routes}
        for _ in range(args.rounds):      # interleaved rounds
            for name, fn in routes.items():
                # paired-delta estimator: (t(2N) - t(N)) / N cancels the
                # fixed dispatch + readback cost that would otherwise
                # dominate these sub-ms ops.
                # A negative delta means host jitter swamped the sample —
                # record it as INVALID (None), never clamp to a fake 0
                # that could win the comparison
                delta = timed_chain(fn, 2 * args.chain) \
                    - timed_chain(fn, args.chain)
                times[name].append(delta / args.chain
                                   if delta > 0 else None)
        int8_bytes = 2 * b * width * h * d          # K + V int8 reads
        fp_bytes = int8_bytes * jnp.dtype(dtype).itemsize
        results[str(width)] = {}
        for name, ts in times.items():
            valid = [t for t in ts if t is not None]
            results[str(width)][name] = {
                "ms": (round(statistics.median(valid) * 1e3, 3)
                       if valid else None),
                "invalid_samples": len(ts) - len(valid),
            }
        results[str(width)]["roofline_ms"] = {
            # pure-traffic lower bounds at the measured copy bandwidth
            # (None when the bandwidth calibration was jitter-swamped)
            "kernel_int8_read": (round(int8_bytes / bw * 1e3, 3)
                                 if bw else None),
            "xla_int8_read_fp_write_fp_read": (round(
                (int8_bytes + 2 * fp_bytes) / bw * 1e3, 3)
                if bw else None),
        }

    widest = str(max(int(w) for w in args.widths.split(",")))
    candidates = [(v["ms"], k) for k, v in results[widest].items()
                  if k != "roofline_ms" and v["ms"] is not None]
    best = min(candidates) if candidates else (None, "no-valid-sample")
    print(json.dumps({
        "metric": "int8_attend_best_route_ms",
        "value": best[0],
        "unit": "ms",
        "vs_baseline": None,
        "best_route": best[1],
        "widths": results,
        "copy_bandwidth_gbs": round(bw / 1e9, 1) if bw else None,
        "config": {"batch": b, "heads": h, "head_dim": d,
                   "dtype": args.dtype, "chain": args.chain,
                   "rounds": args.rounds, "interpret": interpret},
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
