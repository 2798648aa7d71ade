"""Training-step benchmark: ViT-Large pipeline train step on this chip.

Prints ONE JSON line: images/sec trained, steady step ms, achieved
TFLOP/s and MFU (fwd+bwd ~= 3x forward FLOPs, 2*MAC convention), both
peak denominators — the same overhead-aware methodology as bench.py
(steps CHAIN through the (params, opt_state) carry, so N steps + one
fence amortize the fixed dispatch + readback cost).

The reference cannot run this benchmark at all: it is inference-only
(@torch.no_grad on every shard forward). Training here is jax.grad
through the one-program SPMD pipeline (parallel/train.py).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-m", "--model-name", default="google/vit-large-patch16-224")
    p.add_argument("-b", "--batch", default=8, type=int)
    p.add_argument("-u", "--ubatches", default=4, type=int)
    p.add_argument("--steps", default=8, type=int)
    p.add_argument("--mixed-precision", action="store_true",
                   help="f32 master weights + per-step bf16 compute cast "
                        "(parallel/train.py) instead of pure-bf16 params")
    args = p.parse_args()

    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pipeedge_tpu.benchkit.headline import (
        NOMINAL_BF16_PEAK, calibrate_peak_flops as _calibrate_peak_flops,
        model_flops_per_image as _model_flops_per_image)
    from pipeedge_tpu.models import ShardConfig, registry
    from pipeedge_tpu.parallel import spmd, train

    cfg = registry.get_model_config(args.model_name)
    total = registry.get_model_layers(args.model_name)
    entry = registry.get_model_entry(args.model_name)
    family_mod = entry.family
    param_dtype = jnp.float32 if args.mixed_precision else jnp.bfloat16
    stage_params = [family_mod.init_params(
        cfg, ShardConfig(1, total, is_first=True, is_last=True),
        dtype=param_dtype)]
    mesh = spmd.make_pipeline_mesh(1)
    # remat: per-block checkpointing — without it the backward's saved
    # tick activations need ~40 GB HBM on ViT-L (measured OOM vs 15.75G)
    pipe = spmd.build_spmd_pipeline(family_mod.FAMILY, cfg, [(1, total)],
                                    stage_params, mesh, remat=True)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(
        size=(args.ubatches, args.batch, 3, cfg.image_size, cfg.image_size)),
        param_dtype)   # mixed mode casts to bf16 inside the step
    y = jnp.asarray(rng.integers(0, max(cfg.num_labels, 1),
                                 size=(args.ubatches, args.batch)), jnp.int32)

    on_tpu = jax.devices()[0].platform != "cpu"
    peak = _calibrate_peak_flops() if on_tpu else None   # 32x 8192^3
    #                       matmuls — pointless (and minutes) on CPU
    step, opt_state = train.make_train_step(
        pipe, optax.sgd(1e-3), x, mixed_precision=args.mixed_precision)
    params = pipe.params
    params, opt_state, loss = step(params, opt_state, x, y)   # compile
    float(loss)                                               # fence
    reps = args.steps
    tik = time.monotonic()
    for _ in range(reps):
        params, opt_state, loss = step(params, opt_state, x, y)
    final_loss = float(loss)                                  # fence
    dt = (time.monotonic() - tik) / reps
    images = args.ubatches * args.batch
    # fwd+bwd: dL/dx costs one fwd-sized pass, dL/dw another
    flops = 3 * _model_flops_per_image(cfg) * images
    achieved = flops / dt
    device_kind = jax.devices()[0].device_kind
    nominal = NOMINAL_BF16_PEAK.get(device_kind)   # bench.py's table
    print(json.dumps({
        "metric": "vit_large_train_images_per_sec",
        "value": round(images / dt, 1),
        "unit": "images/sec",
        "vs_baseline": None,    # the reference cannot train at all
        "step_ms": round(dt * 1e3, 2),
        "images_per_step": images,
        "final_loss": round(final_loss, 4),
        "achieved_tflops": round(achieved / 1e12, 1),
        "mfu_calibrated": round(achieved / peak, 3) if peak else None,
        # both key spellings, matching bench.py's record exactly
        "calibrated_peak_tflops": round(peak / 1e12, 1) if peak else None,
        "peak_calibrated_tflops": round(peak / 1e12, 1) if peak else None,
        "mfu_nominal": round(achieved / nominal, 3) if nominal else None,
        "peak_nominal_tflops": round(nominal / 1e12, 1) if nominal else None,
        "dtype": ("f32-master/bf16-compute" if args.mixed_precision
                  else "bfloat16"),
        "mixed_precision": args.mixed_precision,
        "device_kind": device_kind,
    }))


if __name__ == "__main__":
    main()
