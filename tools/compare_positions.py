"""Program against the benchmark's float32 reference a position at a time,
at a generation cell's timed sizes (PERF.md section 7, row 29's method).

`benchmark/correct.py` asks whether each greedy token is near the
reference's best; this asks how far the program's logits are from the
reference's, where: one batch of the cell's rows, teacher-forced random ids
from `--seed` through the cell's own pipeline (the prompt in its spans, then
one step a position), the first row's logits after every span and every step
against `benchmark/reference/<family>.forward` over the same ids. The gap of
a position is the largest difference of a logit over the reference row's
range. Prints one JSON line: the median and the worst gap of the spans' last
rows and of the steps, the worst's position, and whether the largest logit
agrees everywhere. A served cell's mix names no batch: one row then, of the
mix's longest prompt and longest answer, under the server's `--max-len`
(the executor's prompt pass runs the same span programs; its row step picks
inside the program and has no logits to compare: `benchmark/correct.py`
holds its tokens).

Usage (from the root of a checkout, on the chip):
    python tools/compare_positions.py --workload keye-vl2.long-batch --seed 7
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--steps", type=int, default=0,
                   help="steps to compare (default: the cell's new tokens)")
    p.add_argument("--root", default=ROOT,
                   help="where BENCHMARK.json and its files are read from "
                   "(a rehearsal's tiny cut)")
    args = p.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from benchmark import correct, run, weights
    from benchmark.runners import common
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode

    common.enable_cache()
    _, ctx = run.context(args.root, args.workload, args.seed, 0.0, False)
    config, traffic = ctx.config, ctx.traffic
    model = config["program_model"]
    dtype = jnp.bfloat16 if config["dtype"] == "bfloat16" else jnp.float32
    rows, prompt_len = traffic.get("batch", 1), traffic["prompt_len"]
    steps, max_len = args.steps or traffic["new_tokens"], \
        traffic.get("max_len")
    if "server_args" in traffic:    # a served mix: its longest request
        prompt_len = max(prompt_len["choices"])
        steps = args.steps or max(traffic["new_tokens"]["log_uniform"])
        served = traffic["server_args"]
        max_len = int(served[served.index("--max-len") + 1])
    path = weights.write(config, ctx.seed, os.path.join(
        ctx.work, "weights", registry.get_model_default_weights_file(model)))
    pipe = decode.build_decode_pipeline(
        model, None, max_len=max_len, dtype=dtype, model_file=path)
    span = pipe.prefill_span
    if not span:
        sys.exit(f"{model} prefills its prompt whole: nothing to compare "
                 "span by span")
    rng = np.random.Generator(np.random.PCG64(ctx.seed))
    ids = rng.integers(0, config["vocab_size"],
                       size=(rows, prompt_len + steps))

    got, caches = {}, pipe._fresh_caches(rows)
    for start in range(0, prompt_len, span):
        out, caches = pipe.extend(ids[:, start:start + span], caches, start,
                                  last_only=True,
                                  per_octave=pipe.job_per_octave)
        got[min(start + span, prompt_len) - 1] = np.asarray(
            out[0, -1], np.float32)
    for pos in range(prompt_len, prompt_len + steps):
        out, caches = pipe.extend(ids[:, pos:pos + 1], caches, pos,
                                  per_octave=pipe.job_per_octave)
        got[pos] = np.asarray(out[0, 0], np.float32)
    del caches

    with np.load(path) as tensors:
        wanted = np.asarray(correct.reference_module(config).forward(
            config, tensors, ids[:1]))[0]
    os.remove(path)

    def gaps(positions):
        out = []
        for pos in positions:
            row = wanted[pos]
            out.append((float(np.max(np.abs(got[pos] - row))
                              / (row.max() - row.min())), pos,
                        int(np.argmax(got[pos])) == int(np.argmax(row))))
        return out

    line = {"workload": args.workload, "seed": args.seed, "rows": rows}
    for name, positions in (
            ("spans", [p for p in got if p < prompt_len]),
            ("steps", [p for p in got if p >= prompt_len])):
        found = gaps(sorted(positions))
        worst = max(found)
        line[name] = {"positions": len(found),
                      "median_gap_share_of_range": statistics.median(
                          g for g, _, _ in found),
                      "worst_gap_share_of_range": worst[0],
                      "worst_at": worst[1],
                      "argmax_agrees": sum(ok for _, _, ok in found)}
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
