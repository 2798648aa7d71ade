"""Offline pipeline inference over staged images: the calls `runtime.py`
makes for `-c host` (`run_pipeline_host`) and `-c spmd`
(`run_pipeline_spmd`), repeated warm in one process.

Why a runner and not the CLI as a child: `runtime.py` makes its images
from a fixed seed of its own, times one round (`-c spmd`: one warm run)
and can trace only its whole life, compile included. The runner builds the
pipeline with the same library calls, stages images made from `--seed`,
repeats the warm round until `--seconds` are over, reads every result back
to the host as `handle_results` does, and brackets a few seconds with the
profiler in a traced run. The traffic file gives the driver (`host` or
`spmd`), the partition, the microbatch, how many images are staged and,
optionally, `quant`: the bits of the edge leaving each stage (0 = none)."""
import math
import os
import time

import numpy as np

from benchmark import correct, device, weights, xplane
from benchmark.runners import common


def _partition(traffic):
    cuts = [int(x) for x in traffic["partition"].split(",")]
    return list(zip(cuts[::2], cuts[1::2]))


def run(ctx):
    import jax
    import jax.numpy as jnp

    from pipeedge_tpu import telemetry
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import pipeline as host_pipeline
    from pipeedge_tpu.parallel import spmd

    common.enable_cache()
    mark = common.Marks(ctx.started)
    mark("imports")
    devices = jax.devices()
    stamp = device.stamp(devices)
    device.require(stamp, ctx.cell["chips"], ctx.platforms)
    devices = devices[:ctx.cell["chips"]]
    mark("devices")

    config, traffic = ctx.config, ctx.traffic
    model = config["program_model"]
    dtype = jnp.bfloat16 if config["dtype"] == "bfloat16" else jnp.float32
    partition = _partition(traffic)
    ubatch = traffic["ubatch"]
    staged = traffic["staged_images"] // ubatch      # microbatches resident
    per_round = traffic["round_images"] // ubatch    # of them, one call's
    shape = (ubatch, config["num_channels"], config["image_size"],
             config["image_size"])
    path = weights.write(config, ctx.seed, os.path.join(
        ctx.work, "weights", registry.get_model_default_weights_file(model)))
    mark("weights_file")
    key = common.seeded_key(ctx.seed)
    if ctx.trace:
        telemetry.configure(rank=0)

    read_back = []      # the newest round's logits, on the host

    if traffic["driver"] == "host":
        pipe = host_pipeline.build_pipeline(
            model, partition, model_file=path,
            devices=[devices[i % len(devices)]
                     for i in range(len(partition))],
            quant_bits=traffic.get("quant", [0] * len(partition)), dtype=dtype)
        pipe.ubatch_callback = \
            lambda i, out: read_back.append(np.asarray(out))
        mark("pipeline_built")
        draw = jax.jit(lambda k: jax.random.normal(k, shape, dtype))
        images = [draw(k) for k in jax.random.split(key, staged)]
        jax.block_until_ready(images)
        mark("images_staged")
        rounds = [images[i:i + per_round]
                  for i in range(0, staged - per_round + 1, per_round)]

        def one_round(index):
            read_back.clear()
            pipe.run(rounds[index % len(rounds)])

        ticks_per_round = per_round
        one_round(0)        # compiles; one round of the shapes the window uses
    else:
        entry = registry.get_model_entry(model)
        stage_params = [registry.module_shard_factory(
            model, path, l, r, stage=i, dtype=dtype, unroll=False)[1]
            for i, (l, r) in enumerate(partition)]
        mesh = spmd.make_pipeline_mesh(len(partition), devices=devices)
        pipe = spmd.build_spmd_pipeline(
            entry.family.FAMILY, entry.config, partition, stage_params,
            mesh, quant_bit=traffic.get("quant", 0))
        del stage_params
        mark("pipeline_built")
        from jax.sharding import NamedSharding, PartitionSpec
        everywhere = NamedSharding(mesh, PartitionSpec())
        chunk = math.gcd(per_round, 64)

        def fill(k):
            # chunk by chunk into one buffer: a single draw of the whole
            # round would hold its random bits beside it
            def body(i, buffer):
                part = jax.random.normal(jax.random.fold_in(k, i),
                                         (chunk,) + shape, dtype)
                return jax.lax.dynamic_update_slice_in_dim(
                    buffer, part, i * chunk, axis=0)
            return jax.lax.fori_loop(
                0, per_round // chunk, body,
                jnp.zeros((per_round,) + shape, dtype))

        draw = jax.jit(fill, out_shardings=everywhere)
        rounds = [draw(k) for k in jax.random.split(key, staged // per_round)]
        jax.block_until_ready(rounds)
        mark("images_staged")

        def one_round(index):
            read_back.clear()
            read_back.extend(np.asarray(pipe.run(rounds[index % len(rounds)])))

        ticks_per_round = per_round + len(partition) - 1
        one_round(0)        # compiles

    mark("warm_round")

    def sample_inputs(index):
        """The first microbatch of round `index`, as the reference wants."""
        return np.asarray(rounds[index % len(rounds)][0], np.float32)

    if ctx.trace:
        telemetry.recorder().drain()
    observed = {"config": config, "traffic": traffic, "stages": len(partition),
                "chips": ctx.cell["chips"]}
    # a traced run first measures a short window with the profiler off, for
    # the per-layer metrics that the host's clock gives, then brackets a
    # shorter round with the profiler
    seconds = ctx.seconds if not ctx.trace \
        else min(ctx.seconds, traffic.get("layer_seconds", 8.0))
    done, ends = 0, []
    first = time.monotonic()
    setup_s = first - ctx.started
    while time.monotonic() - first < seconds:
        one_round(done)
        done += 1
        ends.append(time.monotonic() - first)
    window_s = ends[-1]
    round_s = sorted(b - a for a, b in zip([0.0] + ends, ends))
    if ctx.trace:
        observed["spans"] = telemetry.recorder().drain()
        telemetry.disable()
        short = traffic.get("trace_round_images", traffic["round_images"]) \
            // ubatch
        if traffic["driver"] == "host":
            traced = lambda: pipe.run(rounds[0][:short])
        else:
            few = rounds[0][:short]
            pipe.run(few)       # this length's program compiles here
            traced = lambda: np.asarray(pipe.run(few))
        trace_dir = os.path.join(ctx.work, "trace")
        kept = list(read_back)
        calls = common.traced_window(
            trace_dir, traffic.get("trace_seconds", 3.0), traced)
        read_back[:] = kept
        observed["trace_images"] = calls * short * ubatch
        observed["trace"] = xplane.reduce_dir(trace_dir)
    images_done = done * per_round * ubatch
    observed.update(window_s=window_s, images=images_done,
                    ticks=done * ticks_per_round,
                    microbatches=done * per_round)

    # correctness, outside the window: the first microbatch of the newest
    # round against the float32 reference on the same weights file
    last = done - 1
    with np.load(path) as tensors:
        ok, facts = correct.logits_agree(
            config, tensors, sample_inputs(last), read_back[0])
    os.remove(path)
    stamp["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    return common.Outcome(
        correct=ok, attempted=images_done, failed=0, device=stamp,
        end_to_end={"img_per_s": images_done / window_s, "setup_s": setup_s},
        observed=observed,
        notes={"reference": facts, "rounds": done, "window_s": window_s,
               "round_images": per_round * ubatch,
               "round_s_median": round(round_s[len(round_s) // 2], 4),
               "round_s_max": round(round_s[-1], 4),
               "setup_marks": mark.at})
