"""Trajectory differ + CI regression gate over benchkit records.

Compares two trajectory records — or two multi-scenario artifacts
(BENCH_r0N.json), matched by scenario — metric by metric with per-metric
noise bands, prints ONE JSON line, and with `--gate` exits nonzero on
any regression: the per-PR proof that a claimed win (or an innocent
refactor) did not quietly cost goodput, attainment, latency, MFU, or
agreement.

Gated metrics (direction-aware):
- `throughput`                      higher is better
- `latency_ms.p50/p95/p99`          lower is better
- `mfu.calibrated`                  higher is better
- `quality.top1_agreement_vs_exact` higher is better
- `serve.goodput_rps.<class>`       higher is better
- `serve.slo_attainment.<class>`    higher is better
- `serve.shed.error`                ZERO tolerance (any error regresses)

Noise bands: each metric's band starts from the per-metric default,
which a baseline record may REPLACE per metric via its own
`noise_bands` map ({metric-path-prefix: band}, longest prefix wins) —
the committed-baseline author's way to TIGHTEN a band below the
default for metrics that record has shown to be stable (ROADMAP item:
calibrated noise bands instead of one-size-fits-all). The effective
band is then the LARGEST of that, (a) the baseline record's own
relative spread when it carries samples (`throughput.spread` — the
honest per-session wobble the record measured about itself), and
(b) any `--noise NAME=FRACTION` override. Overrides match by plain
string prefix on the metric path (longest match wins): `--noise
serve.goodput=0.5` covers every `serve.goodput_rps.<class>`, `--noise
latency_ms=2.0` covers all three percentiles, `--noise
throughput=0.5` covers only `throughput`. An override that matches NO
metric in any compared scenario is reported to stderr — a typo must
not silently leave the default band in force.
A change within the band is noise; beyond it against the metric's
direction is a regression; beyond it in favor is an improvement
(reported, never gated).

Config fingerprints: records compare apples-to-apples only when their
config fingerprints match. A mismatch is a warning by default (CPU smoke
vs chip headline have different configs on purpose) and an error under
`--strict-config`.

Exit codes: 0 clean (or no --gate), 1 regression(s) under --gate,
2 input/usage error (unreadable record, no common scenarios, fingerprint
mismatch under --strict-config).

Examples:
    # two rounds of the multi-scenario artifact
    python tools/bench_report.py BENCH_r07.json --baseline BENCH_r06.json

    # CI bench-smoke gate against the committed baseline, generous
    # throughput band (shared runners), tight attainment band
    python tools/bench_report.py bench_records.json \
        --baseline tools/bench_baseline.json --gate \
        --noise throughput=0.6 --noise serve.goodput_rps=0.6
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pipeedge_tpu.benchkit import schema  # noqa: E402

# metric path prefix -> (direction, default noise band). Direction +1:
# higher is better; -1: lower is better. First matching prefix wins
# (ordered longest-first at lookup).
METRIC_DEFAULTS: Dict[str, Tuple[int, float]] = {
    "throughput": (+1, 0.10),
    "latency_ms.p50": (-1, 0.25),
    "latency_ms.p95": (-1, 0.35),
    "latency_ms.p99": (-1, 0.50),
    "mfu.calibrated": (+1, 0.10),
    "quality.top1_agreement_vs_exact": (+1, 0.005),
    "serve.goodput_rps": (+1, 0.20),
    # attainment is machine-independent (a fraction of admitted
    # requests, not a rate) — 5% is plenty even on shared runners
    "serve.slo_attainment": (+1, 0.05),
    "serve.shed.error": (-1, 0.0),
    "kv.errors": (-1, 0.0),
    "kv.decode_p99_ms": (-1, 0.50),
    "kv.chunked.burst_decode_p99_ms": (-1, 0.50),
    "kv.chunked.goodput_rps": (+1, 0.20),
    "kv.chunked.attainment": (+1, 0.05),
}


def extract_metrics(record: dict) -> Dict[str, float]:
    """Flatten a trajectory record into {metric_path: value} for every
    gateable metric present and non-null."""
    out: Dict[str, float] = {}

    def put(path: str, val) -> None:
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            out[path] = float(val)

    thr = record.get("throughput") or {}
    put("throughput", thr.get("value"))
    lat = record.get("latency_ms") or {}
    for q in ("p50", "p95", "p99"):
        put(f"latency_ms.{q}", lat.get(q))
    mfu = record.get("mfu") or {}
    put("mfu.calibrated", mfu.get("calibrated"))
    quality = record.get("quality") or {}
    put("quality.top1_agreement_vs_exact",
        quality.get("top1_agreement_vs_exact"))
    serve = record.get("serve") or {}
    for cls, val in (serve.get("goodput_rps") or {}).items():
        put(f"serve.goodput_rps.{cls}", val)
    for cls, val in (serve.get("slo_attainment") or {}).items():
        put(f"serve.slo_attainment.{cls}", val)
    put("serve.shed.error", (serve.get("shed") or {}).get("error"))
    kv = record.get("kv") or {}
    put("kv.errors", kv.get("errors"))
    for phase, val in (kv.get("decode_p99_ms") or {}).items():
        put(f"kv.decode_p99_ms.{phase}", val)
    chunked = kv.get("chunked") or {}
    put("kv.chunked.burst_decode_p99_ms",
        chunked.get("burst_decode_p99_ms"))
    put("kv.chunked.goodput_rps", chunked.get("goodput_rps"))
    put("kv.chunked.attainment", chunked.get("attainment"))
    return out


def _override_band(overrides: Dict[str, float],
                   path: str) -> Optional[float]:
    """Plain string-prefix match, longest prefix wins (the documented
    --noise semantics: 'serve.goodput' covers serve.goodput_rps.*)."""
    for prefix in sorted(overrides, key=len, reverse=True):
        if path.startswith(prefix):
            return overrides[prefix]
    return None


def metric_direction(path: str) -> int:
    for prefix in sorted(METRIC_DEFAULTS, key=len, reverse=True):
        if path == prefix or path.startswith(prefix + "."):
            return METRIC_DEFAULTS[prefix][0]
    return +1


def noise_band(path: str, baseline: dict,
               overrides: Dict[str, float]) -> float:
    """max(record band, record's own measured spread, override), where
    the record band is the baseline record's per-metric `noise_bands`
    entry (longest-prefix match) when present — it REPLACES the
    per-metric default, so a committed baseline can tighten a band
    below the one-size-fits-all default — else the default."""
    override = _override_band(overrides, path)
    band = 0.10
    for prefix in sorted(METRIC_DEFAULTS, key=len, reverse=True):
        if path == prefix or path.startswith(prefix + "."):
            band = METRIC_DEFAULTS[prefix][1]
            break
    record_bands = baseline.get("noise_bands")
    if isinstance(record_bands, dict):
        record_band = _override_band(
            {k: float(v) for k, v in record_bands.items()}, path)
        if record_band is not None:
            band = record_band
    if path == "throughput":
        thr = baseline.get("throughput") or {}
        spread = thr.get("spread")
        if (isinstance(spread, (list, tuple)) and len(spread) == 2
                and thr.get("value")):
            rel = abs(spread[1] - spread[0]) / max(1e-9, thr["value"])
            band = max(band, rel)
    if override is not None:
        band = max(band, override)
    return band


def compare_records(base: dict, new: dict,
                    overrides: Optional[Dict[str, float]] = None) -> dict:
    """Per-metric verdicts for one scenario pair. Metrics present in the
    baseline but MISSING from the new record are regressions (a metric
    cannot silently vanish past the gate); metrics new in `new` are
    reported as `new` and never gated."""
    overrides = overrides or {}
    base_m = extract_metrics(base)
    new_m = extract_metrics(new)
    metrics: Dict[str, dict] = {}
    regressed: List[str] = []
    for path in sorted(set(base_m) | set(new_m)):
        b, n = base_m.get(path), new_m.get(path)
        if b is None:
            metrics[path] = {"new": n, "verdict": "new"}
            continue
        if n is None:
            metrics[path] = {"base": b, "verdict": "missing"}
            regressed.append(path)
            continue
        band = noise_band(path, base, overrides)
        direction = metric_direction(path)
        if b:
            delta = (n - b) / abs(b)
        else:
            # zero baseline: any move is infinitely large relative to it
            # (e.g. serve.shed.error going 0 -> 3 must regress)
            delta = 0.0 if n == b else float("inf") * (1 if n > b else -1)
        worse = -delta * direction  # positive = worse, as a fraction
        if worse > band:
            verdict = "regressed"
            regressed.append(path)
        elif -worse > band:
            verdict = "improved"
        else:
            verdict = "ok"
        metrics[path] = {
            "base": b, "new": n,
            "delta_pct": (round(delta * 100, 2)
                          if abs(delta) != float("inf") else None),
            "band_pct": round(band * 100, 2),
            "verdict": verdict,
        }
    return {
        "scenario": new.get("scenario", base.get("scenario")),
        "config_match": (base.get("config_fingerprint")
                         == new.get("config_fingerprint")),
        "metrics": metrics,
        "regressed": regressed,
        "ok": not regressed,
    }


def _load_records(path: str) -> Dict[str, dict]:
    with open(path, encoding="utf8") as fh:
        doc = json.load(fh)
    records = schema.records_from_any(doc)
    for scenario, record in records.items():
        problems = schema.validate_record(record)
        if problems:
            raise ValueError(f"{path}: invalid {scenario!r} record: "
                             f"{problems}")
    return records


def _parse_noise(pairs) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for pair in pairs or ():
        name, _, frac = pair.partition("=")
        try:
            out[name] = float(frac)
        except ValueError:
            raise SystemExit(f"--noise expects NAME=FRACTION, got "
                             f"{pair!r}") from None
        if not 0.0 <= out[name] <= 10.0:
            raise SystemExit(f"--noise fraction out of range: {pair!r}")
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("new", help="new record / multi-scenario artifact")
    p.add_argument("--baseline", required=True,
                   help="baseline record / artifact to diff against")
    p.add_argument("--gate", action="store_true",
                   help="exit 1 when any common scenario regresses "
                        "(the CI bench-smoke mode)")
    p.add_argument("--noise", action="append", metavar="NAME=FRACTION",
                   help="per-metric-prefix noise-band override, e.g. "
                        "throughput=0.5 (repeatable; max with defaults)")
    p.add_argument("--strict-config", action="store_true",
                   help="fail (exit 2) when a compared pair's config "
                        "fingerprints differ instead of warning")
    p.add_argument("--scenario", action="append",
                   help="restrict the diff to these scenarios "
                        "(repeatable; default: every common one)")
    p.add_argument("--indent", action="store_true",
                   help="pretty-print instead of the one-line record")
    args = p.parse_args(argv)
    overrides = _parse_noise(args.noise)

    try:
        base_all = _load_records(args.baseline)
        new_all = _load_records(args.new)
    except (OSError, ValueError) as exc:
        print(f"bench_report: {exc}", file=sys.stderr)
        return 2
    common = sorted(set(base_all) & set(new_all))
    if args.scenario:
        missing = set(args.scenario) - set(common)
        if missing:
            print(f"bench_report: scenario(s) not present in both "
                  f"inputs: {sorted(missing)}", file=sys.stderr)
            return 2
        common = sorted(args.scenario)
    if not common:
        print(f"bench_report: no common scenarios between "
              f"{args.baseline} ({sorted(base_all)}) and "
              f"{args.new} ({sorted(new_all)})", file=sys.stderr)
        return 2

    scenarios = {}
    regressed: List[str] = []
    seen_paths: set = set()
    for scenario in common:
        diff = compare_records(base_all[scenario], new_all[scenario],
                               overrides)
        scenarios[scenario] = diff
        seen_paths.update(diff["metrics"])
        if not diff["config_match"]:
            msg = (f"bench_report: {scenario}: config fingerprints "
                   "differ (baseline "
                   f"{base_all[scenario].get('config_fingerprint')}, new "
                   f"{new_all[scenario].get('config_fingerprint')})")
            if args.strict_config:
                print(msg, file=sys.stderr)
                return 2
            print(f"{msg} — diffing anyway", file=sys.stderr)
        regressed.extend(f"{scenario}:{m}" for m in diff["regressed"])

    # a --noise override that matched nothing is almost certainly a typo
    # (the band the operator thinks is in force isn't) — say so
    for name in sorted(overrides):
        if not any(path.startswith(name) for path in seen_paths):
            print(f"bench_report: --noise {name}=... matched no metric "
                  f"(known paths: {', '.join(sorted(seen_paths))})",
                  file=sys.stderr)

    report = {
        "baseline": args.baseline,
        "new": args.new,
        "scenarios": scenarios,
        "scenarios_only_in_baseline": sorted(set(base_all) - set(new_all)),
        "scenarios_only_in_new": sorted(set(new_all) - set(base_all)),
        "regressed": regressed,
        "ok": not regressed,
    }
    print(json.dumps(report, indent=2 if args.indent else None,
                     sort_keys=True))
    if regressed:
        print("bench_report: REGRESSED: " + ", ".join(regressed),
              file=sys.stderr)
        return 1 if args.gate else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
