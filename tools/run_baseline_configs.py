"""Run the five BASELINE.md configs on the current backend and print one
JSON line per config.

Configs (BASELINE.md "Configs to reproduce"):
  1. ViT-Base single-rank
  2. ViT-Base 2-stage even partition (-pt 1,24,25,48)
  3. ViT-Large 4-stage, auto-partition from a TPU profile via sched-pipeline
  4. BERT-base CoLA 2-stage
  5. DeiT-Base 8-stage + adaptive int8 (QuantPipe)

On a single chip the host driver places every stage on the same device
(round-robin, parallel/pipeline.py:247), so multi-stage configs measure the
full pipeline machinery (stage hand-off, quant edges, adaptive policy) at
single-chip scale; datasets are synthetic under zero egress (the loaders
fall back when ImageNet/GLUE are absent, utils/data.py).

Usage: python tools/run_baseline_configs.py [-c N] [--platform cpu]
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIGS = {
    1: {"desc": "vit-base single-rank",
        "args": ["0", "1", "-m", "google/vit-base-patch16-224",
                 "-b", "64", "-u", "8"]},
    2: {"desc": "vit-base 2-stage even partition",
        "args": ["0", "2", "-m", "google/vit-base-patch16-224",
                 "-b", "64", "-u", "8", "-pt", "1,24,25,48"]},
    3: {"desc": "vit-large 4-stage auto-partition (profiles/tpu)",
        "args": ["0", "4", "-m", "google/vit-large-patch16-224",
                 "-b", "64", "-u", "8",
                 "-sm", os.path.join(REPO, "profiles", "tpu", "models.yml"),
                 "-sdt", os.path.join(REPO, "profiles", "tpu",
                                      "device_types.yml"),
                 "-sd", os.path.join(REPO, "profiles", "tpu", "devices.yml"),
                 "-H", "tpu0,tpu1,tpu2,tpu3"]},
    4: {"desc": "bert-base CoLA 2-stage",
        "args": ["0", "2", "-m", "textattack/bert-base-uncased-CoLA",
                 "-b", "64", "-u", "8", "-pt", "1,24,25,48",
                 "--dataset-name", "CoLA"]},
    5: {"desc": "deit-base 8-stage + adaptive int8",
        "args": ["0", "8", "-m", "facebook/deit-base-distilled-patch16-224",
                 "-b", "64", "-u", "8",
                 "-pt", "1,6,7,12,13,18,19,24,25,30,31,36,37,42,43,48",
                 "-q", "8,8,8,8,8,8,8,0"],
        "env": {"ADAPTIVE_QUANT": "HEURISTIC", "SEND_CONSTRAINT": "1000"}},
}


def run_config(n: int, platform: str, dtype: str) -> dict:
    spec = CONFIGS[n]
    missing = [a for a in spec["args"]
               if a.endswith(".yml") and not os.path.exists(a)]
    if missing:
        return {"config": n, "desc": spec["desc"], "rc": "missing-profiles",
                "missing": missing,
                "hint": "generate the TPU profile fixtures first "
                        "(profiles/README.md)"}
    cmd = [sys.executable, os.path.join(REPO, "runtime.py")] + spec["args"] \
        + ["-t", dtype, "--measure-rounds", "2"]
    if platform:
        cmd += ["--platform", platform]
    pypath = os.pathsep.join(
        p for p in (REPO, os.environ.get("PYTHONPATH")) if p)
    env = dict(os.environ, PYTHONPATH=pypath, **spec.get("env", {}))
    tik = time.monotonic()
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, env=env, timeout=1800)
    except subprocess.TimeoutExpired:
        return {"config": n, "desc": spec["desc"], "rc": "timeout",
                "wall_s": round(time.monotonic() - tik, 1)}
    wall = time.monotonic() - tik
    result = {"config": n, "desc": spec["desc"], "rc": proc.returncode,
              "wall_s": round(wall, 1)}
    rounds = re.findall(r"round=(\d+) latency_sec=([0-9.]+) "
                        r"throughput_items_sec=([0-9.]+)", proc.stdout)
    match = re.search(r"latency_sec=([0-9.]+) throughput_items_sec=([0-9.]+)",
                      proc.stdout)
    if rounds:
        # round 0 = cold (XLA compiles included, the reference's
        # single-shot methodology); last round = warm steady state
        result["cold_latency_sec"] = float(rounds[0][1])
        result["cold_items_per_sec"] = float(rounds[0][2])
        result["latency_sec"] = float(rounds[-1][1])
        result["items_per_sec"] = float(rounds[-1][2])
    elif match:
        result["latency_sec"] = float(match.group(1))
        result["items_per_sec"] = float(match.group(2))
    else:
        result["tail"] = (proc.stdout + proc.stderr)[-400:]
    return result


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("-c", "--config", type=int, action="append",
                        choices=sorted(CONFIGS),
                        help="configs to run (default: all)")
    parser.add_argument("--platform", default=None,
                        help="force a jax platform (e.g. cpu)")
    parser.add_argument("-t", "--dtype", default="bfloat16",
                        choices=["bfloat16", "float32"])
    args = parser.parse_args()
    for n in args.config or sorted(CONFIGS):
        print(json.dumps(run_config(n, args.platform, args.dtype)),
              flush=True)


if __name__ == "__main__":
    main()
