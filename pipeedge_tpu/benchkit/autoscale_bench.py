"""The autoscale A/B recipe: advise vs auto under one seeded load ramp.

The self-driving-capacity question is not "can the controller spawn a
replica" (tools/chaos_dcn.py --target autoscale proves that under
chaos) — it is "what does closing the loop BUY": the same seeded
piecewise-linear ramp (`loadgen --arrival ramp:LO:HI[:HOLD]`) is offered
twice against an identical 1-replica-floor router fleet, once with the
controller in `--autoscale advise` (decisions logged, nothing actuated —
the control arm) and once in `--autoscale auto` (decisions applied).
The record carries both arms side by side: time-to-scale-up, per-class
SLO attainment during the ramp, aggregate goodput, and the decision
count, so `bench_report --gate` catches a controller that stopped
scaling (attainment/goodput collapse to the advise arm's numbers) or
started flapping (decision count explodes) the same way it catches a
throughput regression.

Mechanics per arm: spawn `tools/serve.py --role router` parked at the
floor with `--max-active 1` replicas (one replica's honest capacity is
a few req/s, so the ramp's plateau genuinely queues), warm the floor
replica with the exact load shape (an unwarmed page-boundary XLA
compile masquerades as a capacity shortfall), offer the ramp, then for
the auto arm wait for the drain back to the floor. Both arms share the
loadgen seed: identical arrival offsets and prompts, so the A/B delta
is the controller, not the traffic.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
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


class _Reader:
    """Timestamped line capture off a subprocess's merged stdout (the
    router narrates `autoscale_spawn` / `autoscale_decision` lines; the
    timestamps turn them into time-to-scale-up)."""

    def __init__(self, proc):
        self.proc = proc
        self.lines = []
        self._t = threading.Thread(target=self._pump, daemon=True)
        self._t.start()

    def _pump(self):
        for line in self.proc.stdout:
            self.lines.append((time.monotonic(), line.rstrip("\n")))

    def join(self):
        self._t.join(timeout=5)

    def first(self, *prefixes):
        for t, line in self.lines:
            if line.startswith(prefixes):
                return t, line
        return None


def _autoscale_args(p) -> None:
    p.add_argument("--model", default="pipeedge/test-tiny-gpt2")
    p.add_argument("--partition", default="1,4,5,8",
                   help="pipeline layer partition (serve.py -pt)")
    p.add_argument("--max-len", type=int, default=64)
    p.add_argument("--ramp", default="ramp:1:8:0.4",
                   help="seeded piecewise-linear arrival spec offered "
                        "identically to BOTH arms")
    p.add_argument("--duration", type=float, default=12.0,
                   help="seconds of ramp per arm")
    p.add_argument("--new-tokens", type=int, default=24,
                   help="decode tokens per request (24 keeps one "
                        "--max-active 1 replica's capacity around "
                        "~3 req/s so the ramp's plateau queues)")
    p.add_argument("--prompt-len", type=int, default=6)
    p.add_argument("--seed", type=int, default=7,
                   help="loadgen seed shared by both arms (identical "
                        "arrivals + prompts)")
    p.add_argument("--floor", type=int, default=1)
    p.add_argument("--ceiling", type=int, default=2)
    p.add_argument("--kv-pages", type=int, default=96)
    p.add_argument("--kv-page-size", type=int, default=8)
    p.add_argument("--settle-s", type=float, default=60.0,
                   help="post-ramp wait for the auto arm's drain back "
                        "to the floor")
    p.add_argument("--startup-timeout", type=float, default=180.0)


def _spawn_fleet(args, mode: str):
    port = _free_port()
    cmd = [sys.executable, os.path.join(REPO, "tools", "serve.py"),
           "--role", "router", "--replicas", str(args.floor),
           "-m", args.model, "-pt", args.partition,
           "--max-len", str(args.max_len), "-t", "float32",
           "--port", str(port),
           "--kv-pages", str(args.kv_pages),
           "--kv-page-size", str(args.kv_page_size),
           "--max-active", "1",
           "--router-poll-interval", "0.2",
           "--fleet-scrape-interval", "0.3",
           "--autoscale", mode,
           "--autoscale-min", str(args.floor),
           "--autoscale-max", str(args.ceiling),
           "--autoscale-confirm", "2",
           "--autoscale-cooldown", "2.0",
           "--autoscale-interval", "0.3",
           "--autoscale-dwell-down", "1.0",
           "--autoscale-queue-high", "2.0",
           "--autoscale-queue-low", "0.5"]
    # the server inherits this process's platform, so the record's env
    # stamp (taken here after teardown) names the device the work ran on
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    return proc, f"http://127.0.0.1:{port}"


def _get_json(url: str, path: str, timeout=10.0):
    with urllib.request.urlopen(f"{url}{path}", timeout=timeout) as resp:
        return json.loads(resp.read())


def _run_arm(args, mode: str, loadgen) -> dict:
    proc, url = _spawn_fleet(args, mode)
    reader = _Reader(proc)
    try:
        deadline = time.monotonic() + args.startup_timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"{mode} arm router died during startup")
            try:
                h = _get_json(url, "/healthz", timeout=5)
                if h.get("ok") and all(r["state"] == "healthy"
                                       for r in h["fleet"].values()):
                    break
            except OSError:
                time.sleep(0.5)
        else:
            raise RuntimeError(f"{mode} arm fleet never became healthy")
        # warm with the exact load shape: the first request crossing a
        # KV page boundary pays a multi-second XLA compile, and an
        # unwarmed compile stall reads as a capacity shortfall
        payload = json.dumps({"ids": [7] * args.prompt_len,
                              "new_tokens": args.new_tokens}).encode()
        for rep in h["fleet"].values():
            req = urllib.request.Request(
                f"{rep['url']}/generate", data=payload,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                resp.read()
        load_t0 = time.monotonic()
        report = loadgen.run_load(
            f"{url}/generate", args.duration, None,
            mix={"interactive": 1.0}, deadline_from_slo=False,
            new_tokens=args.new_tokens, prompt_len=str(args.prompt_len),
            seed=args.seed, arrival=args.ramp)
        scale_down_s = None
        if mode == "auto":
            settle_deadline = time.monotonic() + args.settle_s
            while time.monotonic() < settle_deadline:
                a = _get_json(url, "/healthz",
                              timeout=5).get("autoscale") or {}
                if a.get("size") == args.floor and (
                        a.get("decisions") or {}).get("applied", 0) >= 2:
                    scale_down_s = round(
                        time.monotonic() - load_t0 - args.duration, 3)
                    break
                time.sleep(0.5)
        asnap = _get_json(url, "/healthz",
                          timeout=5).get("autoscale") or {}
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
        reader.join()
    # the actuated arm's first spawn vs the advisory arm's first logged
    # up-decision: both are "when did the controller move", comparable
    first_up = (reader.first("autoscale_spawn") if mode == "auto"
                else next(((t, line) for t, line in reader.lines
                           if line.startswith("autoscale_decision")
                           and "direction=up" in line), None))
    classes = report["classes"]
    goodput = {c: classes[c]["goodput_rps"] for c in classes}
    goodput["total"] = round(sum(goodput.values()), 3)
    decisions = asnap.get("decisions") or {}
    return {
        "mode": mode,
        "requests": report["requests"],
        "offered_qps": report["offered_qps"],
        "ramp": report.get("ramp"),
        "goodput_rps": goodput,
        "slo_attainment": {c: classes[c]["slo_attainment"]
                           for c in classes},
        "shed": dict({k: report["totals"][k] for k in SHED_TAXONOMY},
                     client_dropped=report["client_dropped"]),
        "latency_ms": {q: report["latency_ms"][q]
                       for q in ("p50", "p95", "p99", "n")},
        "decisions": decisions,
        "decision_count": sum(decisions.values()),
        "ticks": asnap.get("ticks"),
        "final_size": asnap.get("size"),
        "time_to_first_up_s": (round(first_up[0] - load_t0, 3)
                               if first_up else None),
        "scale_down_s": scale_down_s,
    }


def _run(args) -> dict:
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools import loadgen

    advise = _run_arm(args, "advise", loadgen)
    auto = _run_arm(args, "auto", loadgen)

    notes = None
    errs = advise["shed"]["error"] + auto["shed"]["error"]
    if errs:
        notes = f"{errs} handler error(s) across the two arms"
    att_delta = {
        c: round(auto["slo_attainment"][c]
                 - advise["slo_attainment"].get(c, 0.0), 4)
        for c in auto["slo_attainment"]}
    return {
        # the headline is the CLOSED-LOOP arm: what the fleet actually
        # delivers when the controller is allowed to act
        "throughput": {"value": auto["goodput_rps"]["total"],
                       "unit": "req/s",
                       "detail": "aggregate goodput under the seeded "
                                 "ramp, --autoscale auto arm"},
        "latency_ms": auto["latency_ms"],
        "serve": {
            "goodput_rps": auto["goodput_rps"],
            "slo_attainment": auto["slo_attainment"],
            "shed": auto["shed"],
            "offered_qps": auto["offered_qps"],
            "requests": auto["requests"],
            "ramp": args.ramp,
            "seed": args.seed,
            "floor": args.floor,
            "ceiling": args.ceiling,
        },
        "notes": notes,
        "extras": {
            "ab": {"advise": advise, "auto": auto},
            "time_to_scale_up_s": auto["time_to_first_up_s"],
            "advise_first_up_s": advise["time_to_first_up_s"],
            "scale_down_s": auto["scale_down_s"],
            "decision_count": {"advise": advise["decision_count"],
                               "auto": auto["decision_count"]},
            "attainment_delta_auto_minus_advise": att_delta,
        },
    }


def _register():
    from . import Recipe, register
    register(Recipe(
        "autoscale", "advise-vs-auto capacity-controller A/B under one "
                     "seeded load ramp: time-to-scale-up, attainment "
                     "during the ramp, decision counts",
        _autoscale_args, _run, tier="fleet"))


_register()
