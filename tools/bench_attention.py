"""A/B the Pallas fused-attention kernel against XLA's einsum attention
on the current backend. Prints one JSON line with a row per sequence
length — the recorded evidence behind `_use_fused_attention`'s policy
(pipeedge_tpu/models/layers.py): XLA wins short sequences, the
flash-attention kernel wins long ones by keeping each query block's
scores resident in VMEM (HBM traffic O(S*D) instead of O(S^2)).

Usage: python tools/bench_attention.py [-s 512,2048,8192] [-b 1] [--heads 16]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, q, k, v, reps=25):
    """ms per call: chain the output back in as the next query (serializing
    executions on-device) and fence ONCE with a scalar readback — a
    per-rep fence would add the fixed dispatch + readback cost to every
    measurement."""
    import jax.numpy as jnp
    fence = lambda x: float(jnp.sum(x.astype(jnp.float32)))
    fence(fn(q, k, v))                  # compile + warm (fence warmed too)
    o = fn(q, k, v)
    fence(o)
    tik = time.monotonic()
    o = q
    for _ in range(reps):
        o = fn(o, k, v)
    fence(o)
    return (time.monotonic() - tik) / reps * 1e3


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-s", "--seq-lens", default="512,2048,8192")
    p.add_argument("-b", "--batch", default=1, type=int)
    p.add_argument("--heads", default=16, type=int)
    p.add_argument("--head-dim", default=64, type=int)
    p.add_argument("--causal", action="store_true")
    args = p.parse_args()

    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pipeedge_tpu.ops.attention import (attention_is_supported,
                                            fused_attention)
    interpret = not attention_is_supported()   # CPU smoke runs interpret

    @jax.jit
    def xla_attend(q, k, v):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
        if args.causal:
            s = q.shape[1]
            qp = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
            kp = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
            scores = jnp.where((kp <= qp)[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    rows = {}
    rng = np.random.default_rng(0)
    for s in (int(x) for x in args.seq_lens.split(",")):
        shape = (args.batch, s, args.heads, args.head_dim)
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                   for _ in range(3))
        xla_ms = _time(xla_attend, q, k, v)
        pallas_ms = _time(
            lambda q, k, v: fused_attention(q, k, v, causal=args.causal,
                                            interpret=interpret),
            q, k, v)
        rows[str(s)] = {"xla_ms": round(xla_ms, 3),
                        "pallas_ms": round(pallas_ms, 3),
                        "speedup": round(xla_ms / pallas_ms, 2)}
    longest = rows[max(rows, key=int)]
    print(json.dumps({
        "metric": "fused_attention_speedup",
        "value": longest["speedup"],
        "unit": "x (XLA/pallas at longest S)",
        "vs_baseline": None,
        "batch": args.batch, "heads": args.heads,
        "head_dim": args.head_dim, "causal": args.causal,
        "dtype": "bfloat16", "per_seq_len": rows,
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
