"""Open-loop load generator for the serving plane (docs/SERVING.md).

Drives tools/serve.py's /generate with a per-class request mix at a
target aggregate arrival rate and reports ONE JSON line (the
chaos_dcn.py idiom) of per-class SLO attainment, goodput, and shed
accounting — the "proof under fire" for the admission/brownout plane:
at 5x sustained overload, interactive goodput should hold while the
excess converts to 503s with a dynamic Retry-After, not to collapse.

OPEN loop: arrivals are scheduled on the clock (every 1/qps seconds),
not gated on completions — the honest overload model. A server that
slows down does not slow the offered load down with it; requests the
client cannot even launch (in-flight cap, a safety valve) are counted
separately as `client_dropped` so a wedged server cannot silently look
like a polite one.

Each request carries its class and a deadline budget (`deadline_ms`,
defaulting to the class SLO): the server sheds it at admission, expires
it in queue, or cancels it mid-flight (HTTP 504) when the budget runs
out — every outcome lands in a distinct counter below.

Outcome taxonomy (per class and aggregate):
- `ok`        HTTP 200 within the class SLO (client-side wall time)
- `ok_late`   HTTP 200, but over the SLO (admitted yet too slow — the
              failure mode admission control exists to prevent)
- `shed`      HTTP 503 with `"shed": true` + Retry-After (admission)
- `degraded`  HTTP 503 with `"degraded": true` (failover window)
- `deadline`  HTTP 504 (expired MID-FLIGHT; cancelled at a decode step)
- `error`     anything else — handler exceptions, connection failures,
              malformed bodies; the CI smoke gates on error == 0

`slo_attainment` = ok / (ok + ok_late): of the requests the server chose
to serve, how many met their SLO. `goodput_rps` = ok / duration: the
rate of USEFUL work — the acceptance metric ("within 20% of the
uncontended value at 5x overload"). Shed requests hurt neither; that is
the point of shedding.

Capacity calibration: `--overload-factor F` first measures the server's
closed-loop sequential service rate for `--calibrate-s` seconds, then
offers F times it — "5x overload" stays 5x on any machine. `--qps`
skips calibration.

Examples:
  # calibrated 5x overload, default 70/20/10 mix, 2s SLOs
  python tools/loadgen.py --port 8321 --overload-factor 5 --duration 8

  # explicit rate + per-class mix/SLO
  python tools/loadgen.py --port 8321 --qps 40 --duration 10 \
      --mix interactive=0.5 --mix batch=0.3 --mix best_effort=0.2 \
      --slo interactive=1000 --slo batch=5000
"""
import argparse
import json
import os
import random
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pipeedge_tpu.serving import (REQUEST_CLASSES,  # noqa: E402
                                  parse_class_map)
from pipeedge_tpu.utils.threads import make_lock  # noqa: E402

DEFAULT_MIX = {"interactive": 0.7, "batch": 0.2, "best_effort": 0.1}
DEFAULT_SLO_MS = {"interactive": 2000.0, "batch": 10000.0,
                  "best_effort": 30000.0}
OUTCOMES = ("ok", "ok_late", "shed", "degraded", "deadline", "error")


def _post(url, obj, timeout):
    """POST JSON; returns (status, body-dict, retry_after | None)."""
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), None
    except urllib.error.HTTPError as exc:
        ra = exc.headers.get("Retry-After")
        try:
            body = json.loads(exc.read())
        except Exception:       # noqa: BLE001 — non-JSON error body
            body = {}
        return exc.code, body, None if ra is None else float(ra)


def calibrate(url, seconds, new_tokens, prompt_len, timeout, seed=0):
    """Closed-loop sequential service rate (requests/s): the capacity
    baseline `--overload-factor` multiplies. The first request is
    discarded as compile warmup. Distribution specs calibrate at their
    LONGEST length (capacity should not be flattered by short draws)."""
    rng = random.Random(seed)
    ids = [[rng.randrange(100) for _ in range(spec_max_len(prompt_len))]]
    body = {"ids": ids, "new_tokens": new_tokens, "class": "interactive"}
    status, _, _ = _post(url, body, timeout)          # warmup (compile)
    if status != 200:
        raise RuntimeError(f"calibration warmup failed: HTTP {status}")
    n = 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        status, _, _ = _post(url, body, timeout)
        if status != 200:
            raise RuntimeError(f"calibration request failed: HTTP {status}")
        n += 1
    dt = time.monotonic() - t0
    if n == 0:
        raise RuntimeError(
            f"calibration made no complete request in {seconds}s")
    return n / dt


WORST_N = 5      # per-class worst-latency request ids kept in the report


def parse_prompt_spec(s):
    """`--prompt-len DIST` -> spec dict. Forms:

    - `N`                    fixed length N (the historical behavior)
    - `uniform:LO:HI`        length drawn per request from [LO, HI]
    - `shared:PFX:TOTAL[:POOL]`  every prompt is TOTAL tokens whose
      first PFX tokens are one of POOL (default 1) DETERMINISTIC shared
      prefixes (seed-derived, so reruns share the same prefixes) — the
      workload shape that exercises the server's prefix trie and
      long-context token-budget admission (docs/SERVING.md).

    Accepts an int/dict unchanged (the in-process callers)."""
    if isinstance(s, dict):
        return s
    if isinstance(s, int) or (isinstance(s, str) and s.isdigit()):
        n = int(s)
        if n < 1:
            raise ValueError("prompt length must be >= 1")
        return {"dist": "fixed", "len": n}
    parts = str(s).split(":")
    try:
        if parts[0] == "uniform" and len(parts) == 3:
            lo, hi = int(parts[1]), int(parts[2])
            if not 1 <= lo <= hi:
                raise ValueError
            return {"dist": "uniform", "lo": lo, "hi": hi}
        if parts[0] == "shared" and len(parts) in (3, 4):
            pfx, total = int(parts[1]), int(parts[2])
            pool = int(parts[3]) if len(parts) == 4 else 1
            if not (1 <= pfx < total and pool >= 1):
                raise ValueError
            return {"dist": "shared", "prefix": pfx, "total": total,
                    "pool": pool}
    except ValueError:
        pass
    raise ValueError(
        f"bad --prompt-len {s!r}: expected N, uniform:LO:HI, or "
        "shared:PFX:TOTAL[:POOL]")


def parse_burst_spec(s):
    """`--burst AT:N:LEN[:WINDOW]` -> spec dict (None passes through).

    At fraction AT of the run (0..1), N interactive requests with
    LEN-token prompts launch back-to-back — a seeded long-prompt spike
    riding an otherwise steady run. The spike's own outcomes/latencies
    report under `burst`; served steady-state requests launched inside
    the WINDOW seconds after the spike (default 2.0) report separately
    as `burst.during_ms` — the decode-latency-under-burst number the
    chunked-prefill A/B compares (docs/SERVING.md)."""
    if s is None or isinstance(s, dict):
        return s
    parts = str(s).split(":")
    try:
        if len(parts) in (3, 4):
            at, cnt, ln = float(parts[0]), int(parts[1]), int(parts[2])
            window = float(parts[3]) if len(parts) == 4 else 2.0
            if 0.0 <= at <= 1.0 and cnt >= 1 and ln >= 1 and window > 0:
                return {"at": at, "n": cnt, "len": ln,
                        "window_s": window}
    except ValueError:
        pass
    raise ValueError(
        f"bad --burst {s!r}: expected AT:N:LEN[:WINDOW_S] "
        "with AT a fraction in [0, 1]")


def spec_max_len(spec) -> int:
    """Longest prompt a spec can emit (capacity/calibration sizing)."""
    spec = parse_prompt_spec(spec)
    return {"fixed": spec.get("len"), "uniform": spec.get("hi"),
            "shared": spec.get("total")}[spec["dist"]]


def prompt_ids(spec, rng, base_seed: int):
    """One request's prompt token list under `spec`. Shared prefixes
    derive from `base_seed` + the drawn pool index ONLY — every request
    (and every rerun with the same seed) that draws pool index k gets
    byte-identical prefix tokens, which is what makes the server-side
    prefix-hit counters deterministic."""
    spec = parse_prompt_spec(spec)
    if spec["dist"] == "fixed":
        return [rng.randrange(100) for _ in range(spec["len"])]
    if spec["dist"] == "uniform":
        n = rng.randint(spec["lo"], spec["hi"])
        return [rng.randrange(100) for _ in range(n)]
    pool_idx = rng.randrange(spec["pool"])
    pfx_rng = random.Random(1_000_003 * base_seed + 7919 * pool_idx + 13)
    prefix = [pfx_rng.randrange(100) for _ in range(spec["prefix"])]
    suffix = [rng.randrange(100)
              for _ in range(spec["total"] - spec["prefix"])]
    return prefix + suffix


class _Stats:
    """Per-class outcome/latency accumulator (one lock, short holds)."""

    def __init__(self, classes, during_window=None):
        self._lock = make_lock("loadgen.stats")
        self.counts = {c: dict.fromkeys(OUTCOMES, 0) for c in classes}
        self.latencies = {c: [] for c in classes}     # ok + ok_late, ms
        # served latencies of requests LAUNCHED inside [lo, hi] seconds
        # from start — the burst spike's blast-radius window
        self.during_window = during_window
        self.during = []
        # per-class worst-N (latency_ms, rid) of served requests: the
        # cross-reference from a load run into trace_report --request
        # and the flight recorder's postmortem bundles
        self.worst = {c: [] for c in classes}
        # request ids the server answered 504 (each one triggered a
        # postmortem bundle server-side)
        self.deadline_rids = []
        self.retry_after = []
        self.client_dropped = 0
        self.first_error = None

    def record(self, cls, outcome, latency_ms=None, retry_after=None,
               error=None, rid=None, offset=None):
        with self._lock:
            self.counts[cls][outcome] += 1
            if latency_ms is not None:
                self.latencies[cls].append(latency_ms)
                if (self.during_window is not None and offset is not None
                        and self.during_window[0] <= offset
                        <= self.during_window[1]):
                    self.during.append(latency_ms)
                if rid is not None:
                    w = self.worst[cls]
                    w.append((latency_ms, rid))
                    w.sort(reverse=True)
                    del w[WORST_N:]
            if outcome == "deadline" and rid is not None \
                    and len(self.deadline_rids) < WORST_N:
                self.deadline_rids.append(rid)
            if retry_after is not None:
                self.retry_after.append(retry_after)
            if error is not None and self.first_error is None:
                self.first_error = f"{cls}: {error}"

    def drop(self):
        with self._lock:
            self.client_dropped += 1


def _percentile(vals, q):
    if not vals:
        return None
    vals = sorted(vals)
    idx = min(len(vals) - 1, max(0, int(round(q / 100.0 * (len(vals) - 1)))))
    return round(vals[idx], 3)


def parse_ramp_spec(spec):
    """`ramp:LO:HI[:HOLD]` -> {"lo", "hi", "hold"}, or None when `spec`
    is a plain arrival-process name. LO/HI are the offered req/s at the
    ramp floor and plateau; HOLD is the plateau's share of the run in
    [0, 1) (default 1/3). The offered rate rises LO->HI over the first
    (1-HOLD)/2 of the run, holds at HI, then falls symmetrically back
    to LO — the autoscale test workload (scale up on the rise, hold
    through the plateau, scale back down on the fall)."""
    if spec is None or not str(spec).startswith("ramp:"):
        return None
    parts = str(spec).split(":")
    if len(parts) not in (3, 4):
        raise ValueError(f"bad ramp spec {spec!r} "
                         "(expected ramp:LO:HI[:HOLD])")
    try:
        lo, hi = float(parts[1]), float(parts[2])
        hold = float(parts[3]) if len(parts) == 4 else 1.0 / 3.0
    except ValueError:
        raise ValueError(f"bad ramp spec {spec!r}: LO/HI/HOLD must be "
                         "numbers") from None
    if lo <= 0 or hi < lo:
        raise ValueError(f"bad ramp spec {spec!r}: need 0 < LO <= HI")
    if not 0.0 <= hold < 1.0:
        raise ValueError(f"bad ramp spec {spec!r}: HOLD is the plateau "
                         "fraction of the run, in [0, 1)")
    return {"lo": lo, "hi": hi, "hold": hold}


def ramp_rate(t, duration_s, ramp):
    """Instantaneous offered rate (req/s) `t` seconds into a
    `ramp:LO:HI[:HOLD]` run: piecewise-linear rise -> hold -> fall."""
    edge = duration_s * (1.0 - ramp["hold"]) / 2.0
    lo, hi = ramp["lo"], ramp["hi"]
    if t < edge:                      # edge > 0 whenever this is reached
        return lo + (hi - lo) * (t / edge)
    if t <= duration_s - edge:
        return hi
    if t >= duration_s:
        return lo
    return hi - (hi - lo) * ((t - (duration_s - edge)) / edge)


def _ramp_offsets(duration_s, ramp):
    # Step `t += 1/rate(t)` across the run so the instantaneous spacing
    # tracks the piecewise-linear offered rate. Deterministic — the
    # ramp analogue of the uniform grid; `seed` still drives the class
    # draw and prompt sampling, so a run is reproducible end-to-end.
    offsets, t = [], 0.0
    while t < duration_s:
        offsets.append(t)
        t += 1.0 / ramp_rate(t, duration_s, ramp)
    return offsets


def arrival_offsets(n, qps, arrival="uniform", rng=None, duration_s=None):
    """Seconds-from-start launch time of each of `n` arrivals at mean
    rate `qps`. `uniform` is the fixed 1/qps grid (the historical
    behavior); `poisson` draws seeded exponential gaps — an open-loop
    memoryless arrival process whose bursts stress the admission queue
    harder than a metronome at the same mean rate. `ramp:LO:HI[:HOLD]`
    (see `parse_ramp_spec`) ignores `n`/`qps` and shapes the rate over
    `duration_s` instead — the arrival count falls out of the rate
    integral. Pure: same (n, qps, arrival, rng seed) -> same offsets,
    so a load run is reproducible end-to-end from its seed (which rides
    the report)."""
    ramp = parse_ramp_spec(arrival)
    if ramp is not None:
        if duration_s is None or duration_s <= 0:
            raise ValueError("ramp arrival needs duration_s > 0")
        return _ramp_offsets(duration_s, ramp)
    if arrival == "uniform":
        return [i / qps for i in range(n)]
    if arrival != "poisson":
        raise ValueError(f"unknown arrival process {arrival!r} "
                         "(expected 'uniform', 'poisson' or "
                         "'ramp:LO:HI[:HOLD]')")
    if rng is None:
        rng = random.Random(0)
    offsets, t = [], 0.0
    for _ in range(n):
        offsets.append(t)
        t += rng.expovariate(qps)
    return offsets


def _one_request(url, cls, slo_ms, deadline_ms, new_tokens, prompt_spec,
                 timeout, stats, rng_seed, base_seed, offset=None):
    rng = random.Random(rng_seed)
    ids = [prompt_ids(prompt_spec, rng, base_seed)]
    body = {"ids": ids, "new_tokens": new_tokens, "class": cls}
    if deadline_ms is not None:
        body["deadline_ms"] = deadline_ms
    t0 = time.monotonic()
    try:
        status, resp, retry_after = _post(url, body, timeout)
    except Exception as exc:    # noqa: BLE001 — connection-level failure
        stats.record(cls, "error", error=repr(exc))
        return
    ms = (time.monotonic() - t0) * 1e3
    rid = resp.get("rid") if isinstance(resp, dict) else None
    if status == 200:
        outcome = "ok" if (slo_ms is None or ms <= slo_ms) else "ok_late"
        stats.record(cls, outcome, latency_ms=ms, rid=rid, offset=offset)
    elif status == 503 and resp.get("shed"):
        stats.record(cls, "shed", retry_after=retry_after, rid=rid)
    elif status == 503 and resp.get("degraded"):
        stats.record(cls, "degraded", retry_after=retry_after, rid=rid)
    elif status == 504 and resp.get("deadline_exceeded"):
        stats.record(cls, "deadline", rid=rid)
    else:
        stats.record(cls, "error",
                     error=f"HTTP {status}: {resp.get('error', resp)!r}")


def run_load(url, duration_s, qps, mix=None, slo_ms=None,
             deadline_from_slo=True, new_tokens=8, prompt_len=6,
             timeout=120.0, max_inflight=128, seed=0,
             arrival="uniform", burst=None):
    """Offer `qps` requests/s for `duration_s` with the per-class `mix`;
    return the report dict (see module doc for the outcome taxonomy).
    Importable — the overload acceptance test and the chaos targets
    (tools/chaos_dcn.py) call this in-process instead of shelling
    out. `seed` drives EVERYTHING random end-to-end (arrival process,
    class draw, prompt token sampling) and rides the report. `burst`
    (see `parse_burst_spec`) injects a seeded mid-run long-prompt spike
    whose own outcomes — and the steady-state latencies inside its
    blast-radius window — report under the `burst` key."""
    mix = dict(DEFAULT_MIX if mix is None else mix)
    unknown = set(mix) - set(REQUEST_CLASSES)
    if unknown:
        raise ValueError(f"unknown classes in mix: {sorted(unknown)}")
    total_w = sum(mix.values())
    ramp = parse_ramp_spec(arrival)
    if total_w <= 0 or duration_s <= 0 \
            or (ramp is None and (qps is None or qps <= 0)):
        raise ValueError("mix weights, qps and duration must be > 0")
    prompt_spec = parse_prompt_spec(prompt_len)
    slo_ms = dict(DEFAULT_SLO_MS if slo_ms is None else slo_ms)
    burst = parse_burst_spec(burst)
    burst_at_s = None if burst is None else burst["at"] * duration_s
    classes = sorted(mix)
    weights = [mix[c] / total_w for c in classes]
    stats = _Stats(classes,
                   during_window=None if burst is None else
                   (burst_at_s, burst_at_s + burst["window_s"]))
    # the spike's own accounting stays OUT of the per-class stats: the
    # steady-state goodput/attainment/latency numbers must measure the
    # same offered load with and without --burst
    burst_stats = None if burst is None else _Stats(["interactive"])
    burst_threads = []

    def _fire_burst():
        # back-to-back, NOT semaphore-gated: the spike must hit the
        # server even when the client is at its in-flight cap
        for j in range(burst["n"]):
            def bwork(j=j):
                _one_request(url, "interactive",
                             slo_ms.get("interactive"),
                             slo_ms.get("interactive")
                             if deadline_from_slo else None,
                             new_tokens,
                             {"dist": "fixed", "len": burst["len"]},
                             timeout, burst_stats,
                             seed * 7907 + j, seed)
            t = threading.Thread(target=bwork, daemon=True)
            t.start()
            burst_threads.append(t)

    rng = random.Random(seed)
    inflight = threading.Semaphore(max_inflight)
    threads = []
    if ramp is not None:
        offsets = arrival_offsets(0, None, arrival, rng,
                                  duration_s=duration_s)
        n = len(offsets)
        qps = n / duration_s         # mean offered rate, for the report
    else:
        n = max(1, int(round(qps * duration_s)))
        offsets = arrival_offsets(n, qps, arrival, rng)
    t0 = time.monotonic()
    burst_fired = False
    for i in range(n):
        target = t0 + offsets[i]         # open loop: arrivals on the clock
        if burst is not None and not burst_fired \
                and target >= t0 + burst_at_s:
            # the spike launches ON its clock tick, not the next arrival
            bd = t0 + burst_at_s - time.monotonic()
            if bd > 0:
                time.sleep(bd)
            _fire_burst()
            burst_fired = True
        delay = target - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        cls = rng.choices(classes, weights=weights)[0]
        if not inflight.acquire(blocking=False):
            stats.drop()                 # safety valve, not backpressure
            continue
        cls_slo = slo_ms.get(cls)
        deadline = cls_slo if deadline_from_slo else None

        def work(cls=cls, cls_slo=cls_slo, deadline=deadline, i=i):
            try:
                _one_request(url, cls, cls_slo, deadline, new_tokens,
                             prompt_spec, timeout, stats,
                             seed * 100003 + i, seed, offset=offsets[i])
            finally:
                inflight.release()

        t = threading.Thread(target=work, daemon=True)
        t.start()
        threads.append(t)
    if burst is not None and not burst_fired:
        bd = t0 + burst_at_s - time.monotonic()
        if bd > 0:
            time.sleep(bd)
        _fire_burst()
    for t in threads:
        t.join(timeout=timeout)
    for t in burst_threads:
        t.join(timeout=timeout)
    wall = time.monotonic() - t0
    report = {"url": url, "duration_s": round(wall, 3),
              "offered_qps": round(qps, 3), "requests": n,
              "seed": seed, "arrival": arrival,
              "prompt_len": prompt_spec,
              "client_dropped": stats.client_dropped,
              "classes": {}, "totals": dict.fromkeys(OUTCOMES, 0)}
    if ramp is not None:
        # parsed spec echoed alongside the raw `arrival` string: the
        # autoscale CI job reads the shape from here
        report["ramp"] = dict(ramp)
    all_lat = []
    for c in classes:
        counts = stats.counts[c]
        served = counts["ok"] + counts["ok_late"]
        sent = sum(counts.values())
        lat = stats.latencies[c]
        all_lat.extend(lat)
        report["classes"][c] = {
            **counts, "sent": sent,
            "slo_ms": slo_ms.get(c),
            "slo_attainment": (None if not served
                               else round(counts["ok"] / served, 4)),
            "goodput_rps": round(counts["ok"] / wall, 3),
            "latency_ms": {"p50": _percentile(lat, 50),
                           "p95": _percentile(lat, 95),
                           "p99": _percentile(lat, 99)},
            # worst-N served requests BY ID: feed one to
            # `trace_report --request` (or cross-reference it against
            # the server's postmortem bundles) to explain the tail
            "worst": [{"rid": rid, "ms": round(ms, 3)}
                      for ms, rid in stats.worst[c]],
        }
        for k in OUTCOMES:
            report["totals"][k] += counts[k]
    # aggregate served-latency percentiles (per-class views stay under
    # classes.*)
    report["latency_ms"] = {"p50": _percentile(all_lat, 50),
                            "p95": _percentile(all_lat, 95),
                            "p99": _percentile(all_lat, 99),
                            "n": len(all_lat)}
    ra = stats.retry_after
    report["retry_after"] = {
        "n": len(ra), "min": min(ra) if ra else None,
        "max": max(ra) if ra else None,
        "distinct": len({round(v, 3) for v in ra})}
    # 504'd request ids: each one triggered a deadline postmortem bundle
    # server-side — the load-run-to-bundle cross-reference
    report["deadline_rids"] = stats.deadline_rids
    report["first_error"] = stats.first_error
    if burst is not None:
        bc = burst_stats.counts["interactive"]
        blat = burst_stats.latencies["interactive"]
        report["burst"] = {
            "at_s": round(burst_at_s, 3), "n": burst["n"],
            "prompt_len": burst["len"], "window_s": burst["window_s"],
            **{k: bc[k] for k in OUTCOMES},
            # the spike's own end-to-end latencies (long prompt + decode)
            "latency_ms": {"p50": _percentile(blat, 50),
                           "p95": _percentile(blat, 95),
                           "p99": _percentile(blat, 99)},
            # steady-state served latencies launched inside the blast-
            # radius window — THE burst-decode number the chunked-
            # prefill A/B compares
            "during_ms": {"p50": _percentile(stats.during, 50),
                          "p95": _percentile(stats.during, 95),
                          "p99": _percentile(stats.during, 99),
                          "n": len(stats.during)},
            "first_error": burst_stats.first_error,
        }
    return report


def merge_class_map(pairs, what, default):
    """CLI `CLASS=VALUE` pairs merged over `default` (raises ValueError
    on malformed pairs)."""
    return {**default, **parse_class_map(pairs, what)}


def _parse_class_map(pairs, what, default):
    try:
        return merge_class_map(pairs, what, default)
    except ValueError as exc:
        raise SystemExit(str(exc)) from None


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8321)
    p.add_argument("--duration", type=float, default=8.0,
                   help="seconds of offered load")
    rate = p.add_mutually_exclusive_group()
    rate.add_argument("--qps", type=float, default=None,
                      help="explicit aggregate arrival rate")
    rate.add_argument("--overload-factor", type=float, default=None,
                      help="offer FACTOR x the measured sequential "
                           "service rate (see --calibrate-s)")
    p.add_argument("--calibrate-s", type=float, default=3.0,
                   help="closed-loop capacity measurement window used by "
                        "--overload-factor")
    p.add_argument("--mix", action="append", metavar="CLASS=WEIGHT",
                   help=f"per-class arrival weight (default {DEFAULT_MIX})")
    p.add_argument("--slo", action="append", metavar="CLASS=MS",
                   help="per-class SLO (and deadline_ms budget; default "
                        f"{DEFAULT_SLO_MS})")
    p.add_argument("--no-deadline", action="store_true",
                   help="do not send deadline_ms (SLO still scored "
                        "client-side; the server never sheds on expiry)")
    p.add_argument("--new-tokens", type=int, default=8)
    p.add_argument("--prompt-len", default="6",
                   help="prompt-length distribution: N (fixed), "
                        "uniform:LO:HI, or shared:PFX:TOTAL[:POOL] "
                        "(POOL seed-deterministic shared prefixes — "
                        "exercises the server's prefix trie and "
                        "long-context admission); recorded in the "
                        "JSON line")
    p.add_argument("--timeout", type=float, default=120.0)
    p.add_argument("--max-inflight", type=int, default=128,
                   help="client-side thread cap (arrivals beyond it are "
                        "counted as client_dropped, not silently delayed)")
    p.add_argument("--seed", type=int, default=0,
                   help="drives the arrival process, class draw and "
                        "prompt sampling; recorded in the JSON line")
    p.add_argument("--arrival", default="uniform",
                   metavar="{uniform,poisson,ramp:LO:HI[:HOLD]}",
                   help="arrival process: fixed 1/qps grid, seeded "
                        "exponential gaps (bursty open-loop traffic), "
                        "or a piecewise-linear offered-load ramp "
                        "LO->HI->LO req/s with a HOLD-fraction plateau "
                        "(default 1/3) — the autoscale test workload; "
                        "a ramp sets the rate itself, so --qps/"
                        "--overload-factor must be omitted")
    p.add_argument("--burst", default=None, metavar="AT:N:LEN[:WINDOW]",
                   help="inject a seeded long-prompt spike: at fraction "
                        "AT of the run, N interactive requests with "
                        "LEN-token prompts launch back-to-back; the "
                        "spike's outcomes and the steady-state latency "
                        "inside the WINDOW-second blast radius (default "
                        "2.0) ride the JSON line under `burst`")
    p.add_argument("--indent", action="store_true",
                   help="pretty-print instead of the one-line record")
    args = p.parse_args()

    try:
        ramp = parse_ramp_spec(args.arrival)
    except ValueError as exc:
        p.error(str(exc))
    if ramp is None and args.qps is None and args.overload_factor is None:
        p.error("one of --qps / --overload-factor is required (unless "
                "--arrival ramp:LO:HI[:HOLD] sets the offered rate)")
    if ramp is not None and (args.qps is not None
                             or args.overload_factor is not None):
        p.error("--arrival ramp:... sets the offered rate itself; "
                "drop --qps / --overload-factor")

    url = f"http://{args.host}:{args.port}/generate"
    qps = args.qps
    calibrated = None
    if qps is None and args.overload_factor is not None:
        calibrated = calibrate(url, args.calibrate_s, args.new_tokens,
                               args.prompt_len, args.timeout,
                               seed=args.seed)
        qps = calibrated * args.overload_factor
        print(f"calibrated capacity {calibrated:.2f} req/s -> offering "
              f"{qps:.2f} req/s ({args.overload_factor:g}x)",
              file=sys.stderr)
    report = run_load(
        url, args.duration, qps,
        mix=_parse_class_map(args.mix, "--mix", DEFAULT_MIX),
        slo_ms=_parse_class_map(args.slo, "--slo", DEFAULT_SLO_MS),
        deadline_from_slo=not args.no_deadline,
        new_tokens=args.new_tokens, prompt_len=args.prompt_len,
        timeout=args.timeout, max_inflight=args.max_inflight,
        seed=args.seed, arrival=args.arrival, burst=args.burst)
    if calibrated is not None:
        report["calibrated_capacity_rps"] = round(calibrated, 3)
        report["overload_factor"] = args.overload_factor
    print(json.dumps(report, indent=2 if args.indent else None,
                     sort_keys=True))
    errors = report["totals"]["error"] \
        + report.get("burst", {}).get("error", 0)
    return 0 if errors == 0 else 2


if __name__ == "__main__":
    sys.exit(main())
