"""Size the nominal-MFU residual bucket by bucket (round-5 verdict #1).

docs/PERF.md claims the 197->~117 TFLOP/s gap on the ViT-Large headline
bench is structural, split across (a) f32 VPU numerics kept for parity,
(b) S=197 tile padding, and (c) head_dim=64 half-filling the MXU lanes
— but round 4 never SIZED the buckets. This harness measures each one
with interleaved same-session A/Bs over the ViT-L encoder block stack
(24 blocks, D=1024, I=4096 — where ~99% of the model FLOPs live):

- base:    S=197, exact f32 numerics, 16 heads x 64   (the parity path)
- fast:    S=197, fast numerics (model-dtype LN/softmax, tanh GeLU)
           -> sizes the f32-numerics bucket (an EQUIVALENT model up to
           the measured accuracy delta; bench.py records it)
- pad256:  S=256, exact numerics  -> sizes the S=197 tile-padding
           bucket (each variant is scored against its OWN analytic
           FLOPs, so the comparison is efficiency, not work)
- hd128:   S=197, exact, 8 heads x 128 -> sizes the head_dim=64 MXU
           lane-fill bucket (a COST PROBE: same FLOPs, different head
           geometry — not the same model, used only to price the shape)
- stacked: S=256, fast, 8 x 128 -> the combined ceiling

Rounds are interleaved (one timing per variant per round, repeated) so
session drift hits every variant equally — the chip timing discipline
from docs/PERF.md. Prints ONE JSON line with per-variant ms/TFLOPs/MFU
and the derived bucket attribution.
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
    p.add_argument("-b", "--batch", default=8, type=int)
    p.add_argument("-l", "--layers", default=24, type=int)
    p.add_argument("-d", "--hidden", default=1024, type=int)
    p.add_argument("-i", "--inter", default=4096, type=int)
    p.add_argument("--chain", default=8, type=int,
                   help="full-stack passes chained per timing (one fence)")
    p.add_argument("--rounds", default=3, type=int,
                   help="interleaved timing rounds per variant")
    p.add_argument("--extra-seqs", default="",
                   help="comma-separated extra sequence lengths to probe "
                        "as exact-numerics variants (e.g. 200,208 — sizes "
                        "the small-pad end of the S=197 padding bucket)")
    args = p.parse_args()
    try:
        # fail BEFORE any chip compile, not after five warm builds
        extra_seqs = [int(s) for s in args.extra_seqs.split(",") if s]
    except ValueError:
        p.error(f"--extra-seqs must be comma-separated integers, got "
                f"{args.extra_seqs!r}")

    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pipeedge_tpu.benchkit.headline import (
        NOMINAL_BF16_PEAK, calibrate_peak_samples as
        _calibrate_peak_samples)
    from pipeedge_tpu.models.layers import (dense, gelu, layer_norm,
                                            self_attention,
                                            set_fast_numerics)

    d, inter, n_layers, batch = (args.hidden, args.inter, args.layers,
                                 args.batch)
    rng = np.random.default_rng(0)

    def make_params():
        def mat(m, n):
            return {"w": jnp.asarray(rng.normal(scale=0.02, size=(m, n)),
                                     jnp.bfloat16),
                    "b": jnp.zeros((n,), jnp.bfloat16)}

        def ln():
            return {"scale": jnp.ones((d,), jnp.float32),
                    "bias": jnp.zeros((d,), jnp.float32)}

        def block():
            return {"ln_before": ln(), "q": mat(d, d), "k": mat(d, d),
                    "v": mat(d, d), "attn_out": mat(d, d),
                    "ln_after": ln(), "mlp_up": mat(d, inter),
                    "mlp_down": mat(inter, d)}

        return jax.device_put(jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[block() for _ in range(n_layers)]))

    params = make_params()

    def build(seq, heads, fast):
        """One jitted program: `chain` passes of the L-block ViT stack
        (the vit.py sublayer composition) with a scalar fence — built
        under the requested numerics mode (trace-time flag)."""
        def block(p, x):
            normed = layer_norm(p["ln_before"], x, 1e-12)
            ctx = self_attention(
                {"q": p["q"], "k": p["k"], "v": p["v"]}, normed, heads)
            x = dense(p["attn_out"], ctx) + x
            normed = layer_norm(p["ln_after"], x, 1e-12)
            return dense(p["mlp_down"], gelu(dense(p["mlp_up"],
                                                   normed))) + x

        set_fast_numerics(fast)
        try:
            @jax.jit
            def run(p, x):
                def one_pass(x, _):
                    def step(x, bp):
                        return block(bp, x), None

                    x, _ = jax.lax.scan(step, x, p)
                    # keep magnitudes bounded across chained passes
                    return x * jnp.asarray(0.5, x.dtype), None

                x, _ = jax.lax.scan(one_pass, x, None, length=args.chain)
                return jnp.sum(x.astype(jnp.float32))

            x0 = jax.device_put(jnp.asarray(
                rng.normal(size=(batch, seq, d)), jnp.bfloat16))
            float(run(params, x0))          # compile + warm (flag bound)
        finally:
            set_fast_numerics(False)
        return run, x0

    def flops_per_pass(seq):
        per_block = 8 * seq * d * d + 4 * seq * seq * d + 4 * seq * d * inter
        return n_layers * per_block * batch

    variants = {
        "base": build(197, 16, False),
        "fast_numerics": build(197, 16, True),
        "pad256": build(256, 16, False),
        "hd128": build(197, 8, False),
        "stacked": build(256, 8, True),
    }
    seqs = {"base": 197, "fast_numerics": 197, "pad256": 256,
            "hd128": 197, "stacked": 256}
    for s_extra in extra_seqs:
        if f"pad{s_extra}" in variants or s_extra == 197:
            continue     # already a built-in variant; skip the recompile
        variants[f"pad{s_extra}"] = build(s_extra, 16, False)
        seqs[f"pad{s_extra}"] = s_extra

    cal = _calibrate_peak_samples()
    device_kind = jax.devices()[0].device_kind
    nominal = NOMINAL_BF16_PEAK.get(device_kind)

    times = {k: [] for k in variants}
    for _ in range(args.rounds):            # interleaved rounds
        for name, (run, x0) in variants.items():
            tik = time.monotonic()
            float(run(params, x0))
            times[name].append((time.monotonic() - tik) / args.chain)

    out = {}
    for name in variants:
        t = statistics.median(times[name])
        fl = flops_per_pass(seqs[name])
        out[name] = {
            "pass_ms": round(t * 1e3, 3),
            "achieved_tflops": round(fl / t / 1e12, 1),
            "mfu_nominal": (round(fl / t / nominal, 3) if nominal
                            else None),
            "mfu_calibrated": round(fl / t / max(cal), 3),
        }

    base_tf = out["base"]["achieved_tflops"]
    attribution = {
        "f32_numerics_tflops": round(
            out["fast_numerics"]["achieved_tflops"] - base_tf, 1),
        "seq197_padding_tflops": round(
            out["pad256"]["achieved_tflops"] - base_tf, 1),
        "head_dim64_tflops": round(
            out["hd128"]["achieved_tflops"] - base_tf, 1),
        "stacked_all_tflops": round(
            out["stacked"]["achieved_tflops"] - base_tf, 1),
        "note": "each delta is that variant's achieved TFLOP/s minus "
                "base's, per its OWN analytic FLOPs (efficiency, not "
                "work); 'stacked' is all three at once — buckets need "
                "not sum to it (overheads overlap)",
    }

    print(json.dumps({
        "metric": "mfu_bucket_base_tflops",
        "value": base_tf,
        "unit": "TFLOP/s",
        "vs_baseline": None,
        "variants": out,
        "attribution": attribution,
        "calibration_samples_tflops": [round(s / 1e12, 1) for s in cal],
        "peak_nominal_tflops": (round(nominal / 1e12, 1) if nominal
                                else None),
        "config": {"batch": batch, "layers": n_layers, "hidden": d,
                   "inter": inter, "chain": args.chain,
                   "rounds": args.rounds},
        "device_kind": device_kind,
    }))


if __name__ == "__main__":
    main()
