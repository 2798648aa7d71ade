#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: load, warm up, measure `--seconds`, check the
outputs against the plain reference, print, exit. The last line printed is
one JSON object (`correct`, `attempted`, `failed`, `metrics`, `device`, and
`breakdown` in a traced run); what else is worth knowing goes on earlier
lines. `--trace 0` reports the cell's end-to-end metrics; `--trace 1`
reports its per-layer metrics from a run of its own.

Everything that belongs to one cell is found by name: the workload in
`BENCHMARK.json`, its configuration's file, `traffic/<mix>.json`, the runner
the mix names (`runners/<kind>.py`) and one reader per per-layer metric
(`metrics/<name>.py`). A run that finds no accelerator, or fewer chips than
the cell asks for, exits non-zero and prints no result."""
import time

STARTED = time.monotonic()      # set-up counts from here

import argparse                 # noqa: E402
import importlib                # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import math                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import device, manifest as manifest_rules   # noqa: E402
from benchmark.runners import common                       # noqa: E402


def load_reader(path):
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + os.path.basename(path)[:-3].replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def context(root, name, seed, seconds, trace, platforms=("tpu",),
            started=None):
    """The run's context: the cell and the files it names."""
    manifest = manifest_rules.load(root)
    cell = manifest_rules.workload(manifest, name)
    entry = manifest_rules.config_entry(manifest, cell["config"])
    with open(os.path.join(root, entry["file"]), encoding="utf8") as file:
        config = json.load(file)
    path = manifest_rules.traffic_path(root, manifest, cell["traffic"])
    if path is None:
        raise FileNotFoundError(f"no traffic file named {cell['traffic']}")
    with open(path, encoding="utf8") as file:
        traffic = json.load(file)
    work = os.path.join(root, manifest["paths"][0], ".work", name)
    os.makedirs(work, exist_ok=True)
    return manifest, common.Context(
        cell=cell, config=config, traffic=traffic, seed=seed,
        seconds=seconds, trace=trace, work=work,
        started=STARTED if started is None else started,
        platforms=platforms)


def result_line(root, manifest, ctx, outcome):
    """The object the last line carries."""
    name = ctx.cell["name"]
    metrics = {}
    if ctx.trace:
        observed = dict(outcome.observed)
        if outcome.device["platform"] == "tpu":
            observed["peaks"] = device.peaks_for(outcome.device["kind"])
        for metric in manifest_rules.metrics_of(manifest, "per_layer", name):
            read = load_reader(manifest_rules.reader_path(
                root, manifest, metric["name"]))
            value = read(observed)
            if value is not None and math.isfinite(value):
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
    else:
        for metric in manifest_rules.metrics_of(manifest, "end_to_end", name):
            value = outcome.end_to_end[metric["name"]]
            if not math.isfinite(value):
                raise RuntimeError(f"{metric['name']} is {value}: "
                                   f"{outcome.notes}")
            metrics[metric["name"]] = {"value": value,
                                       "unit": metric["unit"]}
    line = {"correct": bool(outcome.correct),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": metrics,
            "device": dict(outcome.device)}
    trace = outcome.observed.get("trace")
    if ctx.trace and trace:
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    return line


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args()
    manifest, ctx = context(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace))
    runner = importlib.import_module(
        "benchmark.runners." + ctx.traffic["runner"])
    outcome = runner.run(ctx)
    line = result_line(ROOT, manifest, ctx, outcome)
    print("notes: " + json.dumps(outcome.notes, default=str), flush=True)
    trace = outcome.observed.get("trace")
    if trace:
        print("busy_s_per_chip: " + json.dumps(trace["busy_s_per_chip"]),
              flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
