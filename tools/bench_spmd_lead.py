"""A round of the SPMD pipeline with and without the edge's lead, by the
round's microbatches (`parallel/spmd.py::edge_lead`).

The evidence behind `EDGE_LEAD_SHARE`. `vit-l.spmd-4stage`'s pipeline (four
stages of six ViT-L blocks, microbatches of 8 in bfloat16, random weights
and images) at each `--rounds` length, built once with the threshold set to
"never" and once to "always" (the module's constant, as the tests set it).
The two programs run in turn, `--reps` times, each call fenced by its
read-back. Prints one JSON line a length: ms a round either way (median),
ms a tick either way by the program's own count, whether the two programs'
logits are the same bits, and what `edge_lead` says for that length.

Usage: python tools/bench_spmd_lead.py [--rounds 32,64,128,256,1024] [--tiny]
`--tiny` runs `pipeedge/test-tiny-vit` on two stages (a rehearsal on the
CPU's virtual devices: no time of it means anything).
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NEVER, ALWAYS = 0.0, 1.0


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rounds", default="32,64,128,256,1024")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import spmd

    model, partition, dtype = ("google/vit-large-patch16-224",
                               [(1, 24), (25, 48), (49, 72), (73, 96)],
                               jnp.bfloat16)
    if args.tiny:
        model, partition, dtype = ("pipeedge/test-tiny-vit",
                                   [(1, 4), (5, 8)], jnp.float32)
    entry = registry.get_model_entry(model)
    cfg = entry.config
    stage_params = [registry.module_shard_factory(
        model, "", l, r, stage=i, dtype=dtype, unroll=False)[1]
        for i, (l, r) in enumerate(partition)]
    pipe = spmd.build_spmd_pipeline(
        entry.family.FAMILY, cfg, partition, stage_params,
        spmd.make_pipeline_mesh(len(partition)))
    del stage_params
    shipped = spmd.EDGE_LEAD_SHARE
    device = jax.devices()[0]
    print(json.dumps({"device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": jax.device_count()}}), flush=True)

    for n_ubatch in [int(n) for n in args.rounds.split(",")]:
        # on the mesh once, as the program's own sharding wants them
        images = jax.device_put(jax.random.normal(
            jax.random.PRNGKey(n_ubatch),
            (n_ubatch, 8, cfg.num_channels, cfg.image_size, cfg.image_size),
            dtype), NamedSharding(pipe.mesh, PartitionSpec()))
        spmd.EDGE_LEAD_SHARE = shipped
        adaptive = spmd.edge_lead(n_ubatch, pipe.n_stages)
        logits, ticks, times = {}, {}, {NEVER: [], ALWAYS: []}
        for share in (NEVER, ALWAYS):
            spmd.EDGE_LEAD_SHARE = share
            ticks[share] = pipe.n_ticks(n_ubatch)
            logits[share] = np.asarray(pipe.run(images))    # compiles
        for _ in range(args.reps):
            for share in (NEVER, ALWAYS):
                spmd.EDGE_LEAD_SHARE = share
                tik = time.perf_counter()
                np.asarray(pipe.run(images))
                times[share].append(time.perf_counter() - tik)
        wait, lead = (statistics.median(times[s]) * 1e3
                      for s in (NEVER, ALWAYS))
        print(json.dumps({
            "microbatches": n_ubatch,
            "round_ms": {"waits": round(wait, 3), "leads": round(lead, 3)},
            "ticks": {"waits": ticks[NEVER], "leads": ticks[ALWAYS]},
            "tick_ms": {"waits": round(wait / ticks[NEVER], 4),
                        "leads": round(lead / ticks[ALWAYS], 4)},
            "lead_gains_pct": round(100 * (wait / lead - 1), 2),
            "same_bits": bool(np.array_equal(logits[NEVER], logits[ALWAYS])),
            "edge_lead": adaptive}), flush=True)
        pipe._compiled.clear()      # a round's two programs, then the next


if __name__ == "__main__":
    main()
