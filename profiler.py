"""Module shard profiler CLI (parity with /root/reference/profiler.py:176-263).

Measures per-layer time and memory on the available TPU/CPU device and
appends to a profiler_results.yml compatible with the reference's converters
and the native sched-pipeline scheduler.
"""
import argparse
import logging
import os

import jax.numpy as jnp
import numpy as np
import yaml

from pipeedge_tpu import profiler as prof
from pipeedge_tpu.models import registry

logger = logging.getLogger(__name__)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def main():
    parser = argparse.ArgumentParser(
        description="Module Shard Profiler",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-o", "--results-yml", default="profiler_results.yml",
                        type=str, help="output YAML file")
    parser.add_argument("-m", "--model-name", type=str,
                        default="google/vit-base-patch16-224",
                        choices=registry.get_model_names(),
                        help="the neural network model for loading")
    parser.add_argument("-M", "--model-file", type=str,
                        help="the model weights file, if not in working directory")
    parser.add_argument("-l", "--layer-start", default=1, type=int,
                        help="start layer")
    parser.add_argument("-L", "--layer-end", type=int,
                        help="end layer; default: last layer in the model")
    parser.add_argument("-s", "--shape-input", type=str, action="append",
                        help="comma-delimited shape input, e.g. '3,224,224' "
                             "(required for start_layer != 1)")
    parser.add_argument("-b", "--batch-size", default=8, type=int,
                        help="batch size")
    parser.add_argument("-t", "--dtype", default="float32",
                        choices=sorted(_DTYPES), help="compute dtype")
    parser.add_argument("-w", "--warmup", action="store_true", default=True,
                        help="perform a warmup iteration")
    parser.add_argument("--no-warmup", action="store_false", dest="warmup")
    parser.add_argument("-i", "--iterations", default=16, type=int,
                        help="iterations to average runtime over")
    parser.add_argument("--exhaustive", action="store_true",
                        help="measure every layer even when structurally "
                             "identical to an already-measured one (the "
                             "default reuses such measurements)")
    parser.add_argument("--trace", type=str, default=None, metavar="DIR",
                        help="capture a JAX profiler trace of the measured "
                             "forwards into DIR")
    args = parser.parse_args()

    dtype = _DTYPES[args.dtype]
    if args.shape_input is not None:
        shapes = [tuple(int(d) for d in shp.split(","))
                  for shp in args.shape_input]
        rng = np.random.default_rng(0)
        tensors = tuple(jnp.asarray(
            rng.normal(size=(args.batch_size,) + shp), dtype=dtype)
            for shp in shapes)
        inputs = tensors if len(tensors) > 1 else tensors[0]
    else:
        inputs = prof.default_inputs(args.model_name, args.batch_size, dtype)

    model_layers = registry.get_model_layers(args.model_name)
    layer_end = args.layer_end if args.layer_end is not None else model_layers
    dtype_name = args.dtype

    if os.path.exists(args.results_yml):
        print("Using existing results file")
        with open(args.results_yml, "r", encoding="utf-8") as yfile:
            profile_results = yaml.safe_load(yfile)
        prof.validate_profile_results(profile_results, args.model_name,
                                      dtype_name, args.batch_size,
                                      model_layers, args.layer_start, layer_end)
    else:
        profile_results = {
            "model_name": args.model_name,
            "dtype": dtype_name,
            "batch_size": args.batch_size,
            "layers": model_layers,
            "profile_data": [],
        }

    from pipeedge_tpu.utils import tracing
    with tracing.trace(args.trace):
        results = prof.profile_layers_individually(
            args.model_name, args.model_file, inputs, args.layer_start,
            layer_end, args.warmup, args.iterations, dtype=dtype,
            reuse_identical=not args.exhaustive)

    profile_results["profile_data"].extend(results)
    profile_results["profile_data"].sort(key=lambda pd: pd["layer"])
    with open(args.results_yml, "w", encoding="utf-8") as yfile:
        yaml.safe_dump(profile_results, yfile, default_flow_style=None,
                       encoding="utf-8")


if __name__ == "__main__":
    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    main()
