#!/usr/bin/env python3
"""Find the highest rate a serving cell's server sustains: one server, the
cell's traffic at each of several rates in turn, one JSON line a rate.

    python3 benchmark/sweep.py --workload gpt2-m.chat-overload --seed 3 \\
        --seconds 40 --rates 3.4,3.8,4.2,4.6,5.2

A rate is sustained when, over the window, no backlog grows (the answers
still outstanding when the last request is sent stay few, and the drain
after it is short), next to nothing is shed, clamped or failed, and the
generator ran on time. The cell's `rate_per_s` is then written into its
traffic file by hand, as 0.8 of the highest such rate for a cell below the
knee and 1.2 of it for one above; a run of the benchmark never searches."""
import time

STARTED = time.monotonic()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import loadgen, run as bench_run     # noqa: E402
from benchmark.runners import serve                 # noqa: E402


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--rates", required=True)
    args = parser.parse_args()
    _, ctx = bench_run.context(ROOT, args.workload, args.seed, args.seconds,
                               False, started=STARTED)
    server, port, path, _ = serve.launch(ctx)
    stamp = server.ask("stats", "stats", 60)
    try:
        for rate in (float(word) for word in args.rates.split(",")):
            traffic = dict(ctx.traffic, rate_per_s=rate)
            requests = loadgen.schedule(traffic, ctx.config["vocab_size"],
                                        args.seconds, args.seed)
            took = loadgen.drive(serve.HOST, port, requests)
            summary = loadgen.summarize(requests)
            last_sent = max(r.sent_s for r in requests
                            if r.sent_s is not None)
            outstanding = sum(1 for r in requests
                              if r.done_s is None or r.done_s > last_sent)
            print(json.dumps({
                "rate_per_s": rate, "requests": len(requests),
                "failed": summary["failed"],
                "outstanding_at_last_send": outstanding,
                "drain_s": took - last_sent,
                "completed_tok_per_s": summary["tokens"] / took,
                **{key: summary[key] for key in (
                    "ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms",
                    "itl_p95_ms", "gen_late_p95_ms", "failures")},
                "device": stamp}), flush=True)
            time.sleep(2.0)
    finally:
        server.stop()
        os.remove(path)


if __name__ == "__main__":
    main()
