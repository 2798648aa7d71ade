"""Offline batch generation: what `tools/generate.py` does for plain greedy
decoding (`build_decode_pipeline`, then `DecodePipeline.generate` on a batch
of prompts), repeated warm in one process.

Why a runner and not the CLI as a child: `tools/generate.py` generates one
timed batch from a fixed seed of its own and exits. The runner makes the
same two calls, draws the prompts of every batch from `--seed`, and repeats
batches until `--seconds` are over. A batch is whole: the window ends with
the batch that crosses `--seconds`, and the rate is tokens over the time
really taken."""
import os
import time

import numpy as np

from benchmark import correct, device, weights, xplane
from benchmark.runners import common


def run(ctx):
    import jax
    import jax.numpy as jnp

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode

    common.enable_cache()
    mark = common.Marks(ctx.started)
    mark("imports")
    devices = jax.devices()
    stamp = device.stamp(devices)
    device.require(stamp, ctx.cell["chips"], ctx.platforms)
    mark("devices")

    config, traffic = ctx.config, ctx.traffic
    model = config["program_model"]
    dtype = jnp.bfloat16 if config["dtype"] == "bfloat16" else jnp.float32
    rows, prompt_len = traffic["batch"], traffic["prompt_len"]
    new_tokens = traffic["new_tokens"]
    path = weights.write(config, ctx.seed, os.path.join(
        ctx.work, "weights", registry.get_model_default_weights_file(model)))
    mark("weights_file")
    pipe = decode.build_decode_pipeline(
        model, None, max_len=traffic["max_len"], dtype=dtype,
        model_file=path)
    mark("pipeline_built")
    rng = np.random.Generator(np.random.PCG64(ctx.seed))

    def prompts():
        return rng.integers(0, config["vocab_size"], size=(rows, prompt_len))

    def one_batch(ids):
        return np.asarray(pipe.generate(ids, new_tokens))

    one_batch(prompts())        # compiles the prefill and every decode bucket
    mark("warm_batch")
    seconds = ctx.seconds if not ctx.trace \
        else min(ctx.seconds, traffic.get("layer_seconds", 8.0))
    batches, out, ends = 0, None, []
    first = time.monotonic()
    setup_s = first - ctx.started
    while time.monotonic() - first < seconds:
        out = one_batch(prompts())
        batches += 1
        ends.append(time.monotonic() - first)
    window_s = ends[-1]
    tokens = batches * rows * new_tokens
    # a batch is one prefill (which yields the first token) and
    # new_tokens - 1 decode steps
    observed = {"config": config, "traffic": traffic, "window_s": window_s,
                "tokens": tokens, "batches": batches,
                "decode_steps": batches * (new_tokens - 1),
                "rows": rows, "prompt_len": prompt_len,
                "new_tokens": new_tokens}
    if ctx.trace:
        trace_dir = os.path.join(ctx.work, "trace")
        short = traffic.get("trace_new_tokens", new_tokens)
        ids = prompts()
        np.asarray(pipe.generate(ids, short))
        calls = common.traced_window(
            trace_dir, traffic.get("trace_seconds", 3.0),
            lambda: np.asarray(pipe.generate(ids, short)))
        observed["trace"] = xplane.reduce_dir(trace_dir)
        observed["trace_decode_steps"] = calls * (short - 1)
        observed["trace_new_tokens"] = short

    # correctness, outside the window: a few rows of the last batch,
    # teacher-forced through the float32 reference
    sample = out[:traffic.get("check_rows", 2)]
    with np.load(path) as tensors:
        ok, facts = correct.tokens_near_greedy(
            config, tensors, sample, [prompt_len] * len(sample))
    ok = ok and out.shape == (rows, prompt_len + new_tokens)
    os.remove(path)
    stamp["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    return common.Outcome(
        correct=ok, attempted=batches * rows, failed=0, device=stamp,
        end_to_end={"tok_per_s": tokens / window_s, "setup_s": setup_s},
        observed=observed,
        notes={"reference": facts, "batches": batches, "window_s": window_s,
               "batch_s": [round(b - a, 3) for a, b in zip([0.0] + ends, ends)],
               "setup_marks": mark.at})
