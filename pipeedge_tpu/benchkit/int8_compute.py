"""`int8_compute` recipe: exact vs fast-numerics vs int8 compute A/B.

Three traces of the SAME streamed image pipeline, timed interleaved so
session drift hits every side equally (the headline A/B discipline):

  exact — f32/bf16 matmuls, exact-parity numerics pinned
          (`set_fast_numerics(False)`, quantize-compute pinned OFF);
  fast  — model-dtype LayerNorm/softmax + tanh GeLU (the PR 9 knob);
  int8  — every tagged dense routed through the block-scaled int8
          Pallas matmul (ops/int8_matmul.py) behind `QuantizeCompute`,
          with Banner clamp alphas calibrated inline from the first
          microbatch (utils/calibrate.py) unless a sidecar is given.

Each non-exact side reports img/s plus top-1 agreement / max-abs logit
delta vs the interleaved exact logits — a quantized number without its
agreement is not self-describing. The headline quality gate for the
int8 side is >= 0.99 top-1 agreement; the chip-window throughput target
(1126 img/s, ViT-L b8) rides the record as `chip_window_target_img_s`
so bench_report trajectories can gate on it (docs/QUANTIZATION.md).

Both numerics knobs are TRACE-time config: each mode gets a fresh jit
wrapper over the raw (unjitted) shard apply, and the finally-blocks pin
exact mode back rather than re-deferring to the environment (the
ADVICE.md r5 env-poisoning lesson, same as headline.py).
"""
import statistics
import time

# ViT-L b8 int8 chip-window target (ISSUE 19 acceptance): recorded, and
# gated only when the backend is a real TPU — a CPU A/B run records the
# agreement evidence without pretending to the throughput claim.
CHIP_WINDOW_TARGET_IMG_S = 1126.0


def _args(p) -> None:
    p.add_argument("--model", default="google/vit-large-patch16-224",
                   help="image-family model to A/B (default: the ViT-L "
                        "headline)")
    p.add_argument("--ubatches", type=int, default=32,
                   help="microbatches in the streamed set (three modes "
                        "run interleaved; smaller than the headline's "
                        "128 keeps the A/B affordable)")
    p.add_argument("--reps", type=int, default=3,
                   help="interleaved timing rounds (median reported)")
    p.add_argument("--block-k", type=int, default=128,
                   help="activation K-block for the block-scaled int8 "
                        "matmul (ops/int8_matmul.py)")
    p.add_argument("--skip-tags", default="",
                   help="comma-separated dense tags kept exact in the "
                        "int8 mode (per-layer opt-out, e.g. attn.out)")
    p.add_argument("--sidecar", default=None,
                   help="calibration sidecar (tools/calibrate.py) for "
                        "the clamp alphas; default: inline calibration "
                        "from the first microbatch")
    p.add_argument("--no-clamp", action="store_true",
                   help="skip activation clamping entirely (no "
                        "calibration pass; pure dynamic block scales)")


def _calibrated_alphas(args, name, x0) -> dict:
    """Clamp alphas for the int8 mode: sidecar if given, else a one-batch
    inline sweep with the tag observer (eager, unrolled)."""
    from ..utils import calibrate
    if args.sidecar:
        return calibrate.load_sidecar(args.sidecar)["alphas"]
    from ..models import registry
    import numpy as np
    fn, params, _ = registry.module_shard_factory(
        name, None, 1, registry.get_model_layers(name), unroll=True)
    raw_fn = getattr(fn, "__wrapped__", fn)
    stats = calibrate.collect_activation_stats(
        raw_fn, params, [np.asarray(x0, np.float32)])
    return calibrate.compute_alphas(stats, bit=8)


def run_int8_compute(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import registry
    from ..models.layers import (QuantizeCompute, set_fast_numerics,
                                 set_quantize_compute)
    from ..ops import int8_matmul
    from .headline import _image_inputs, top1_agreement

    # Pin exact numerics AND quantize-compute OFF before any trace: an
    # inherited PIPEEDGE_FAST_NUMERICS=1 / PIPEEDGE_QUANTIZE_COMPUTE=1
    # would otherwise poison the "exact" side of the A/B (ADVICE.md r5).
    set_fast_numerics(False)
    set_quantize_compute(False)

    def parser_error(msg):
        raise SystemExit(f"bench.py --recipe int8_compute: {msg}")

    name = args.model
    batch = 8
    n_ubatch = args.ubatches
    cfg, metric, xs = _image_inputs(name, parser_error, n_ubatch, batch)

    fn, params, _ = registry.module_shard_factory(
        name, None, 1, registry.get_model_layers(name), dtype=jnp.bfloat16)
    params = jax.device_put(params)
    raw_fn = fn.__wrapped__

    alphas = None
    if not args.no_clamp:
        alphas = _calibrated_alphas(args, name, xs[0])
    skip = frozenset(t for t in args.skip_tags.split(",") if t)
    qc = QuantizeCompute(enabled=True, block_k=args.block_k,
                         skip_tags=skip, clamp_alphas=alphas)

    def make_run_all():
        # fresh jit wrapper (and fresh inner trace via raw_fn) per mode —
        # jit caches by function identity, trace-time flags don't rebind
        @jax.jit
        def run_all(p, xs):
            def step(carry, x):
                logits = raw_fn(p, x)
                return carry + jnp.sum(logits.astype(jnp.float32)), None

            total, _ = jax.lax.scan(step, jnp.float32(0), xs)
            return total

        return run_all

    def probe_logits(p, x):
        return np.asarray(
            jax.jit(lambda p, x: raw_fn(p, x))(p, x).astype(jnp.float32))

    # --- trace + warm all three modes, capturing per-mode logits -------
    run_exact = make_run_all()
    float(run_exact(params, xs))
    logits_exact = probe_logits(params, xs[0])

    set_fast_numerics(True)
    try:
        run_fast = make_run_all()
        float(run_fast(params, xs))
        logits_fast = probe_logits(params, xs[0])
    finally:
        set_fast_numerics(False)

    set_quantize_compute(qc)
    try:
        run_q = make_run_all()
        float(run_q(params, xs))
        logits_q = probe_logits(params, xs[0])
    finally:
        # False, not None — None would re-defer to the env var, and this
        # bench's exact side must stay exact regardless of environment
        set_quantize_compute(False)

    # --- interleaved timing rounds ------------------------------------
    times = {"exact": [], "fast": [], "int8": []}
    for _ in range(args.reps):
        for key, run in (("exact", run_exact), ("fast", run_fast),
                         ("int8", run_q)):
            tik = time.monotonic()
            float(run(params, xs))
            times[key].append(time.monotonic() - tik)
    img = {key: statistics.median(n_ubatch * batch / t for t in ts)
           for key, ts in times.items()}

    fast_agree = top1_agreement(logits_exact, logits_fast)
    int8_agree = top1_agreement(logits_exact, logits_q)

    on_tpu = jax.devices()[0].platform == "tpu"
    extras = {
        "metric": f"int8_{metric}",
        "exact_images_per_sec": round(img["exact"], 3),
        "fast_images_per_sec": round(img["fast"], 3),
        "int8_images_per_sec": round(img["int8"], 3),
        "int8_speedup_vs_exact": round(img["int8"] / img["exact"], 3),
        "fast_speedup_vs_exact": round(img["fast"] / img["exact"], 3),
        "fast_numerics": fast_agree,
        "block_k": args.block_k,
        "skip_tags": sorted(skip),
        "clamp": ("sidecar" if args.sidecar
                  else "off" if args.no_clamp else "inline-1-batch"),
        "kernel": {
            "mode": int8_matmul._mode(),
            "native_available": bool(int8_matmul.kernel_available()),
        },
        "chip_window_target_img_s": CHIP_WINDOW_TARGET_IMG_S,
        # only a real chip window may claim the throughput target; CPU
        # runs record null here and carry the agreement evidence only
        "chip_window_met": (bool(img["int8"] >= CHIP_WINDOW_TARGET_IMG_S)
                            if on_tpu else None),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }
    return {
        "throughput": {"value": extras["int8_images_per_sec"],
                       "unit": "images/sec"},
        "quality": dict(int8_agree),
        "extras": extras,
    }


def _register():
    from . import Recipe, register
    register(Recipe(
        "int8_compute", "exact vs fast-numerics vs int8-compute A/B: "
                        "img/s + top-1 agreement through the block-"
                        "scaled Pallas int8 matmul path",
        _args, run_int8_compute, tier="fast"))


_register()
