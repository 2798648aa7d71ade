#!/usr/bin/env python
"""Offline int8 calibration: sweep a batch through a shard, emit the
scale sidecar next to the checkpoint.

    python tools/calibrate.py -m pipeedge/test-tiny-vit --batch 8 \
        --batches 2 --out /tmp/tiny.int8scales.npz

Prints one JSON line (the chaos_dcn idiom) with the per-tag alphas and
where the sidecar landed. Serve/bench paths load it back with
`utils.calibrate.quantize_compute_from_sidecar` and install the config
via `models.layers.set_quantize_compute` BEFORE building the model.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-m", "--model", default="pipeedge/test-tiny-vit")
    ap.add_argument("--model-file", default=None,
                    help="checkpoint npz (default: the registry's; the "
                         "sidecar lands next to it)")
    ap.add_argument("--layer-start", type=int, default=1)
    ap.add_argument("--layer-end", type=int, default=0,
                    help="0 = all layers")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--batches", type=int, default=2,
                    help="calibration batches swept through the shard")
    ap.add_argument("--bit", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="sidecar path (default: <model-file>.int8scales"
                         ".npz, or ./<model>.int8scales.npz without a "
                         "checkpoint)")
    args = ap.parse_args()

    import numpy as np

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.utils import calibrate, enable_compile_cache

    enable_compile_cache()

    cfg = registry.get_model_config(args.model)
    layer_end = args.layer_end or registry.get_model_layers(args.model)
    rng = np.random.default_rng(args.seed)
    if cfg.model_type in ("vit", "deit"):
        batches = [np.asarray(rng.normal(size=(
            args.batch, cfg.num_channels, cfg.image_size, cfg.image_size)),
            np.float32) for _ in range(args.batches)]
    else:
        batches = [np.asarray(rng.integers(
            0, cfg.vocab_size, size=(args.batch, 16)), np.int64)
            for _ in range(args.batches)]

    alphas, wscales, stats = calibrate.calibrate_shard(
        args.model, args.model_file, args.layer_start, layer_end,
        batches, bit=args.bit)

    out = args.out
    if out is None:
        base = args.model_file or registry.get_model_entry(
            args.model).weights_file or args.model.replace("/", "_")
        out = calibrate.sidecar_path(base)
    calibrate.write_sidecar(out, alphas, wscales, meta={
        "model": args.model, "bit": args.bit, "batch": args.batch,
        "batches": args.batches, "seed": args.seed,
        "layers": [args.layer_start, layer_end]})

    print(json.dumps({
        "bench": "calibrate", "model": args.model, "sidecar": out,
        "bit": args.bit,
        "alphas": {t: round(a, 6) for t, a in sorted(alphas.items())},
        "amax": {t: round(s.amax, 6) for t, s in sorted(stats.items())},
        "weight_scale_tensors": len(wscales),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
