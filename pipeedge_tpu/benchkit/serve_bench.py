"""The goodput-first serve recipe: loadgen vs tools/serve.py at N x
calibrated overload.

The honest headline for a serving plane under heavy traffic is not
img/s — it is per-class goodput and SLO attainment at overload, with the
excess converted to taxonomized sheds instead of collapse (PR 7), and
every p99 bucket cross-linked to a request trace id (PR 10's exemplar
machinery) so a regression names the request class and the dominant
stall, not just a number.

Mechanics: `setup` spawns `tools/serve.py` (loopback, CPU-capable,
`--max-active` pins capacity so "3x overload" is deterministic) with
`--trace-spans`, `run` calibrates the closed-loop sequential service
rate, offers `--overload-factor` times it through `tools/loadgen.py`'s
open-loop generator (seeded arrivals + prompts — reproducible), then
scrapes /metrics for the latency histogram's `# EXEMPLAR` lines (the
p99-bucket -> trace-id link), and `teardown` SIGTERMs the server so it
writes the merged trace. The record's `serve.trace` +
`latency_ms.exemplars` rows make `tools/trace_report.py --request RID`
the one-command "explain this p99" follow-up.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# outcome keys copied into serve.shed (the loadgen taxonomy,
# tools/loadgen.py module doc)
SHED_TAXONOMY = ("shed", "degraded", "deadline", "error", "ok_late")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _serve_args(p) -> None:
    p.add_argument("--model", default="pipeedge/test-tiny-gpt2",
                   help="model tools/serve.py loads (default: the tiny "
                        "CI loopback model)")
    p.add_argument("--partition", default="1,4,5,8",
                   help="pipeline layer partition (serve.py -pt)")
    p.add_argument("--max-len", type=int, default=48)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--max-active", type=int, default=1,
                   help="execution slots (1 pins capacity so the "
                        "overload factor is deterministic)")
    p.add_argument("--replicas", type=int, default=1,
                   help="decode replicas behind a `--role router` "
                        "front-end (serving/router.py); 1 = the classic "
                        "single-process server. The 1-vs-2 A/B arms of "
                        "one overload run are the routed fleet's "
                        "capacity-scaling record (use --scenario-suffix "
                        "to keep both arms in one artifact)")
    p.add_argument("--queue-capacity", type=int, default=16)
    p.add_argument("--overload-factor", type=float, default=3.0,
                   help="offered load as a multiple of the calibrated "
                        "sequential service rate")
    p.add_argument("--overload-factors", default=None,
                   metavar="F1,F2,...",
                   help="sweep MULTIPLE overload factors (e.g. 1,3,5) "
                        "in one run: each factor gets --duration seconds "
                        "of offered load against ONE calibration, and "
                        "the record carries the full goodput-vs-offered-"
                        "load curve (serve.overload_curve) with the "
                        "LAST factor as the headline blocks; overrides "
                        "--overload-factor")
    p.add_argument("--duration", type=float, default=6.0,
                   help="seconds of offered load")
    p.add_argument("--calibrate-s", type=float, default=2.0,
                   help="closed-loop capacity measurement window")
    p.add_argument("--new-tokens", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=6)
    p.add_argument("--mix", action="append", metavar="CLASS=WEIGHT",
                   help="per-class arrival weight (loadgen default mix)")
    p.add_argument("--slo", action="append", metavar="CLASS=MS",
                   help="per-class SLO / deadline budget")
    p.add_argument("--seed", type=int, default=0,
                   help="loadgen seed: arrival process, class draw, and "
                        "prompt sampling (rides the record)")
    p.add_argument("--arrival", default="uniform",
                   choices=["uniform", "poisson"],
                   help="arrival process (seeded; poisson models bursty "
                        "open-loop traffic)")
    p.add_argument("--trace-out", default="bench_serve_trace.json",
                   help="merged span trace the server writes on "
                        "shutdown (trace_report --request input)")
    p.add_argument("--postmortem-dir", default=None,
                   help="flight-recorder bundle dir (serve.py default "
                        "when unset)")
    p.add_argument("--startup-timeout", type=float, default=180.0)
    p.add_argument("--extra-serve-arg", action="append",
                   dest="extra_serve_args", metavar="ARG", default=[],
                   help="extra tools/serve.py argv token, repeatable "
                        "(e.g. --extra-serve-arg=--kv-pages "
                        "--extra-serve-arg=64 arms the paged plane for "
                        "an overload sweep A/B arm)")


def _setup(args) -> dict:
    port = _free_port()
    cmd = [sys.executable, os.path.join(REPO, "tools", "serve.py"),
           "-m", args.model, "-pt", args.partition,
           "--max-len", str(args.max_len), "-t", args.dtype,
           "--port", str(port),
           "--queue-capacity", str(args.queue_capacity),
           "--trace-spans", args.trace_out,
           # brownout watermarks scaled for a 1-slot loopback server:
           # the ladder must engage inside a ~6 s overload window
           "--brownout-queue-high", "4", "--brownout-queue-low", "1",
           "--brownout-p95-high", "0.75", "--brownout-p95-low", "0.3",
           "--brownout-dwell-up", "0.3", "--brownout-dwell-down", "0.7",
           "--brownout-clamp-tokens", "8", "--governor-interval", "0.1"]
    # 0/absent = let the executor choose (the serve_kv recipe's paged
    # servers are page-bounded, not slot-bounded)
    if getattr(args, "max_active", 0):
        cmd += ["--max-active", str(args.max_active)]
    # extra flags a composing recipe appends (serve_kv: --kv-pages ...)
    cmd += list(getattr(args, "extra_serve_args", ()))
    if args.postmortem_dir:
        cmd += ["--postmortem-dir", args.postmortem_dir]
    replicas = getattr(args, "replicas", 1)
    if replicas > 1:
        # the routed arm: same knobs, but serve.py becomes a router
        # front-end forwarding them to `replicas` supervised replica
        # processes (each gets its own --max-active slots, so capacity
        # scales with the fleet)
        cmd += ["--role", "router", "--replicas", str(replicas),
                "--router-poll-interval", "0.3"]
    # the server inherits this process's platform, so the record's env
    # stamp (taken here after teardown) names the device the work ran on
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    state = {"proc": proc, "port": port,
             "url": f"http://127.0.0.1:{port}"}
    # setup owns its cleanup: run_recipe only reaches teardown once setup
    # has RETURNED, so a startup failure must not leak the server process
    try:
        deadline = time.monotonic() + args.startup_timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError("serve.py died during startup:\n"
                                   f"{proc.stdout.read()}")
            try:
                with urllib.request.urlopen(f"{state['url']}/healthz",
                                            timeout=5):
                    break
            except OSError:
                time.sleep(0.5)
        else:
            raise RuntimeError("serve.py never became healthy "
                               f"within {args.startup_timeout}s")
        if replicas > 1:
            # warm EVERY replica directly: the router's deterministic
            # least-loaded tie-break would otherwise leave replica 2+
            # cold and fold its first XLA compile into the measured
            # overload window
            with urllib.request.urlopen(f"{state['url']}/healthz",
                                        timeout=10) as resp:
                fleet = json.loads(resp.read())["fleet"]
            payload = json.dumps({"ids": [7] * args.prompt_len,
                                  "new_tokens": args.new_tokens}).encode()
            for rep in fleet.values():
                req = urllib.request.Request(
                    f"{rep['url']}/generate", data=payload,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=300) as resp:
                    resp.read()
    except BaseException:
        _teardown(state)     # SIGTERM + reap (kill on a wedged server)
        raise
    return state


def _scrape_exemplars(url: str) -> list:
    """`{le, trace_id, value_s}` rows from the server's request-latency
    histogram — the p99-bucket -> trace-id cross-link the record carries
    (pipeedge_tpu/telemetry/metrics.py renders them, parse_exemplars
    reads them back)."""
    from ..telemetry import metrics as prom
    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as resp:
        text = resp.read().decode()
    return prom.parse_exemplars(
        text, "pipeedge_serve_request_latency_seconds")


def _run(args, state) -> dict:
    # tools/ is a sibling top-level package of pipeedge_tpu; both resolve
    # from the repo root, which REPO re-adds for non-repo-cwd callers
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools import loadgen

    url = f"{state['url']}/generate"
    mix = loadgen.merge_class_map(args.mix, "--mix", loadgen.DEFAULT_MIX)
    slo = loadgen.merge_class_map(args.slo, "--slo",
                                  loadgen.DEFAULT_SLO_MS)
    capacity = loadgen.calibrate(url, args.calibrate_s, args.new_tokens,
                                 args.prompt_len, timeout=120.0,
                                 seed=args.seed)
    factors = [args.overload_factor]
    if args.overload_factors:
        factors = [float(f) for f in args.overload_factors.split(",")]
        if not factors or any(f <= 0 for f in factors):
            raise ValueError(f"bad --overload-factors "
                             f"{args.overload_factors!r}")
    # sweep: each factor offers `duration` seconds against the SAME
    # calibration, so the curve is goodput vs offered load on one
    # capacity baseline (ROADMAP item 5's 1x/3x/5x goodput curve);
    # the LAST factor's full report feeds the headline blocks below
    curve = []
    report = None
    for f in factors:
        report = loadgen.run_load(
            url, args.duration, capacity * f, mix=mix, slo_ms=slo,
            new_tokens=args.new_tokens, prompt_len=args.prompt_len,
            seed=args.seed, arrival=args.arrival)
        inter = report["classes"].get("interactive", {})
        curve.append({
            "factor": f,
            "offered_qps": report["offered_qps"],
            "goodput_rps": round(sum(
                c["goodput_rps"] for c in report["classes"].values()), 3),
            "interactive_slo_attainment": inter.get("slo_attainment"),
            "shed": report["totals"]["shed"],
            "deadline": report["totals"]["deadline"],
            "errors": report["totals"]["error"],
            "p99_ms": report["latency_ms"]["p99"],
        })
    report["calibrated_capacity_rps"] = round(capacity, 3)
    report["overload_factor"] = factors[-1]

    exemplars = _scrape_exemplars(state["url"])
    # the worst (highest-value) exemplar is by construction in the
    # bucket the p99 lives in or above it: THE trace id to pull first
    p99_rid = (max(exemplars, key=lambda e: e["value"])["trace_id"]
               if exemplars else None)

    classes = report["classes"]
    goodput = {c: classes[c]["goodput_rps"] for c in classes}
    goodput["total"] = round(sum(goodput.values()), 3)
    attainment = {c: classes[c]["slo_attainment"] for c in classes}
    shed = {k: report["totals"][k] for k in SHED_TAXONOMY}
    shed["client_dropped"] = report["client_dropped"]
    agg = report["latency_ms"]

    notes = None
    if report["totals"]["error"]:
        notes = (f"{report['totals']['error']} handler error(s); first: "
                 f"{report['first_error']}")
    return {
        "throughput": {"value": goodput["total"], "unit": "req/s",
                       "detail": "aggregate goodput (ok responses / "
                                 "wall time) at overload"},
        "latency_ms": {
            "p50": agg["p50"], "p95": agg["p95"], "p99": agg["p99"],
            "n": agg["n"],
            "exemplars": [{"le": e["le"], "trace_id": e["trace_id"],
                           "value_s": e["value"]} for e in exemplars]},
        "serve": {
            "goodput_rps": goodput,
            "slo_attainment": attainment,
            "shed": shed,
            "per_class": classes,
            "offered_qps": report["offered_qps"],
            "requests": report["requests"],
            "calibrated_capacity_rps": report["calibrated_capacity_rps"],
            "overload_factor": factors[-1],
            "replicas": getattr(args, "replicas", 1),
            "overload_curve": curve,
            "retry_after": report["retry_after"],
            "deadline_rids": report["deadline_rids"],
            "p99_exemplar_rid": p99_rid,
            "seed": args.seed,
            "arrival": args.arrival,
            "trace": args.trace_out,
        },
        "notes": notes,
        "extras": {"loadgen": report},
    }


def _teardown(state) -> None:
    if state is None:
        return
    proc = state["proc"]
    if proc.poll() is None:
        # SIGTERM, not kill: the server's handler unwinds through the
        # trace dump (tools/serve.py --trace-spans contract)
        proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10)


def _register():
    from . import Recipe, register
    register(Recipe(
        "serve", "loadgen-driven goodput bench: per-class goodput / SLO "
                 "attainment / shed taxonomy at calibrated overload, "
                 "p99 exemplars cross-linked to the span trace",
        _serve_args, _run, setup=_setup, teardown=_teardown,
        tier="fast"))


_register()
