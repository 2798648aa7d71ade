"""Trajectory-record schema for the benchmark observatory.

Every recipe in `pipeedge_tpu/benchkit/` emits ONE JSON line in the same
schema-versioned envelope, so `BENCH_*.json` is a multi-scenario artifact
that `tools/bench_report.py` can difference across rounds without knowing
which recipe produced a record. The envelope (docs/PERF.md has the full
field reference):

- `schema`        "pipeedge-bench/v1" — bump on ANY field-shape change;
                  records are only comparable within one schema version
- `scenario`      the recipe name (benchkit registry key)
- `config`        the recipe's resolved parameters (model, sizes, knobs)
- `config_fingerprint`  sha256[:12] of the canonical config JSON — two
                  records compare apples-to-apples iff fingerprints match
                  (bench_report warns, and refuses under --strict-config,
                  otherwise)
- `env`           environment stamp: backend platform, device kind/count,
                  python/jax versions — the "which machine was this"
                  block that explains cross-record drift
- `throughput`    {value, unit, samples, spread} — the headline number
- `latency_ms`    {p50, p95, p99, n, exemplars} — exemplars are
                  `{le, trace_id, value_s}` rows linking a latency bucket
                  to a request trace id (`trace_report --request`)
- `quality`       accuracy-beside-throughput block (top-1 agreement, max
                  abs logit delta) for any non-exact variant
- `mfu`           calibrated + nominal MFU with the pinned calibration
                  recipe version (bench headline recipes only)
- `serve`         per-class goodput_rps / slo_attainment / shed taxonomy
                  (the serve recipe's goodput-first block; with
                  --overload-factors also `overload_curve` — one
                  goodput-vs-offered-load row per swept factor)
- `kv`            the paged-KV serving block (serve_kv recipe): prefix
                  hit rate, pages reused/cached, pool occupancy, and
                  decode p99 with/without a concurrent prefill burst
- `notes`         free-form provenance (e.g. the r05 -> r06 gap record)
- `extras`        recipe-specific raw fields, never gated on

`validate_record` is the machine-checkable contract tests and
bench_report share; `artifact_append` maintains the multi-scenario
`BENCH_r0N.json` artifact (one record per scenario, newest wins).
"""
from __future__ import annotations

import hashlib
import json
import math
import sys
import time
from typing import Dict, List, Optional

SCHEMA = "pipeedge-bench/v1"
ARTIFACT_SCHEMA = "pipeedge-bench-artifact/v1"

# envelope keys a recipe's block dict may fill (everything else it
# returns is an error — keeps records greppable across recipes)
BLOCK_KEYS = ("throughput", "latency_ms", "quality", "mfu", "serve",
              "kv", "notes", "extras", "legacy")


def config_fingerprint(config: dict) -> str:
    """sha256[:12] of the canonical (sorted, compact) config JSON: the
    comparability key — bench_report only trusts a diff between records
    whose fingerprints match."""
    blob = json.dumps(config, sort_keys=True,
                      separators=(",", ":")).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def environment_stamp() -> dict:
    """Which machine/backend produced this record: no device, no record.
    Imports jax lazily so schema validation (tests, bench_report) never
    initializes a backend; a recipe that runs its work in a child calls
    this only after the child has exited (one process per chip)."""
    import jax
    devs = jax.devices()
    return {"python": sys.version.split()[0],
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "jax": jax.__version__}


def make_record(scenario: str, config: dict, blocks: dict,
                env: Optional[dict] = None) -> dict:
    """Assemble the envelope. `blocks` may only use BLOCK_KEYS; the
    `legacy` block (exact headline's pre-benchkit record shape) merges
    into the top level so old consumers keep finding `metric`/`value`."""
    unknown = set(blocks) - set(BLOCK_KEYS)
    if unknown:
        raise ValueError(f"recipe returned unknown block(s): "
                         f"{sorted(unknown)} (allowed: {BLOCK_KEYS})")
    record = {
        "schema": SCHEMA,
        "scenario": scenario,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": dict(config),
        "config_fingerprint": config_fingerprint(config),
        "env": environment_stamp() if env is None else env,
    }
    legacy = blocks.get("legacy") or {}
    for key in BLOCK_KEYS:
        if key == "legacy":
            continue
        val = blocks.get(key)
        if val is not None:
            record[key] = val
    # legacy keys merge at top level but never clobber envelope fields
    for key, val in legacy.items():
        record.setdefault(key, val)
    return record


def _check_pcts(lat: dict, problems: List[str]) -> None:
    pcts = [lat.get(k) for k in ("p50", "p95", "p99")]
    nums = [p for p in pcts if p is not None]
    if any(not isinstance(p, (int, float)) or p < 0 for p in nums):
        problems.append("latency_ms percentiles must be numbers >= 0")
        return
    if nums != sorted(nums):
        problems.append(f"latency_ms percentiles not monotonic: {pcts}")
    for row in lat.get("exemplars", ()):
        if not isinstance(row, dict) or "trace_id" not in row \
                or "le" not in row:
            problems.append(f"malformed exemplar row: {row!r}")


def validate_record(record: dict) -> List[str]:
    """The machine-checkable record contract: a list of problems, empty
    when the record is a valid v1 trajectory line. Shared by
    tests/test_benchkit.py and bench_report's input loading."""
    problems: List[str] = []
    if not isinstance(record, dict):
        return [f"record is {type(record).__name__}, not an object"]
    if record.get("schema") != SCHEMA:
        problems.append(f"schema is {record.get('schema')!r}, "
                        f"expected {SCHEMA!r}")
    if not isinstance(record.get("scenario"), str) \
            or not record.get("scenario"):
        problems.append("scenario missing or not a string")
    cfg = record.get("config")
    if not isinstance(cfg, dict):
        problems.append("config missing or not an object")
    else:
        fp = record.get("config_fingerprint")
        if fp != config_fingerprint(cfg):
            problems.append(f"config_fingerprint {fp!r} does not match "
                            "the config block")
    if not isinstance(record.get("env"), dict):
        problems.append("env stamp missing")
    thr = record.get("throughput")
    if thr is not None:
        if not isinstance(thr, dict) or "value" not in thr \
                or "unit" not in thr:
            problems.append("throughput must be {value, unit, ...}")
        elif not isinstance(thr["value"], (int, float)) \
                or not math.isfinite(thr["value"]) or thr["value"] < 0:
            problems.append(f"throughput.value invalid: {thr['value']!r}")
    lat = record.get("latency_ms")
    if lat is not None:
        if not isinstance(lat, dict):
            problems.append("latency_ms must be an object")
        else:
            _check_pcts(lat, problems)
    serve = record.get("serve")
    if serve is not None:
        if not isinstance(serve, dict):
            problems.append("serve must be an object")
        else:
            for key in ("goodput_rps", "slo_attainment"):
                block = serve.get(key)
                if not isinstance(block, dict) or not block:
                    problems.append(f"serve.{key} must be a non-empty "
                                    "per-class object")
            shed = serve.get("shed")
            if shed is not None and not isinstance(shed, dict):
                problems.append("serve.shed must be an object (outcome "
                                "taxonomy counts)")
    quality = record.get("quality")
    if quality is not None:
        agree = quality.get("top1_agreement_vs_exact",
                            quality.get("top1_agreement"))
        if agree is not None and not 0.0 <= float(agree) <= 1.0:
            problems.append(f"quality agreement out of [0, 1]: {agree}")
    return problems


# -- multi-scenario artifact (BENCH_r0N.json) ----------------------------

def artifact_load(path: str) -> dict:
    """Load (or initialize) a multi-scenario artifact."""
    try:
        with open(path, encoding="utf8") as fh:
            doc = json.load(fh)
    except FileNotFoundError:
        return {"schema": ARTIFACT_SCHEMA, "records": []}
    if isinstance(doc, dict) and doc.get("schema") == ARTIFACT_SCHEMA:
        return doc
    raise ValueError(f"{path} is not a {ARTIFACT_SCHEMA} artifact")


def artifact_append(path: str, record: dict) -> dict:
    """Append `record` to the artifact at `path` (created when missing),
    replacing any previous record of the same scenario — re-running one
    recipe re-arms that scenario without touching the others."""
    doc = artifact_load(path)
    doc["records"] = [r for r in doc.get("records", ())
                      if r.get("scenario") != record.get("scenario")]
    doc["records"].append(record)
    with open(path, "w", encoding="utf8") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return doc


def records_from_any(doc) -> Dict[str, dict]:
    """{scenario: record} from any accepted input shape: a single v1
    record, a multi-scenario artifact, or a list of records (JSONL loads
    to this). bench_report's one input loader."""
    if isinstance(doc, dict) and doc.get("schema") == SCHEMA:
        return {doc["scenario"]: doc}
    if isinstance(doc, dict) and doc.get("schema") == ARTIFACT_SCHEMA:
        return {r["scenario"]: r for r in doc.get("records", ())}
    if isinstance(doc, list):
        return {r["scenario"]: r for r in doc}
    raise ValueError("unrecognized bench record shape (expected a "
                     f"{SCHEMA} record, a {ARTIFACT_SCHEMA} artifact, "
                     "or a list of records)")
