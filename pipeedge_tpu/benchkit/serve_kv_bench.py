"""`serve_kv`: the paged-KV serving bench — prefix sharing, page-pool
occupancy, and decode-p99 isolation under concurrent prefill.

Four measured phases against one `tools/serve.py --kv-pages` server
(optionally disaggregated, `--disaggregate local|wire`; optionally
continuous+chunked, `--chunked N`):

1. **prefix burst** — a shared-prefix workload (`loadgen`'s
   `shared:PFX:TOTAL:POOL` prompt distribution): every prompt repeats
   one of POOL deterministic prefixes, so after each prefix's first
   prefill the trie should serve the rest from shared pages. Reported:
   prefix hit rate, pages reused, pool occupancy.
2. **decode solo** — short fixed prompts at a fixed rate: the baseline
   decode p99.
3. **decode + prefill burst** — the SAME short-prompt load while a
   background thread hammers long-prompt requests. The ratio of phase-3
   to phase-2 p99 is the number disaggregation exists to hold down:
   colocated, prefill ticks steal stage-time from decode waves;
   disaggregated, the prefill fleet absorbs them (the A/B in
   docs/evidence/ runs this recipe both ways).
4. **decode + mid-run spike** — the same short-prompt load with
   loadgen's `--burst`: N long prompts launched back-to-back at the
   midpoint. The served latencies inside the spike's blast-radius
   window (`kv.chunked.burst_decode_p99_ms`) are the continuous-
   batching A/B's headline: run `--chunked 0` vs `--chunked N` with
   the same seed — chunked prefill should hold the burst decode p99
   down while goodput/attainment hold.

The record's `kv` block carries all four; `serve`-style goodput/shed
blocks come from phase 1. Gates the CI `kv-serve` smoke cares about:
zero handler errors everywhere, prefix hits > 0.
"""
from __future__ import annotations

import json
import sys
import threading
import urllib.request

from .serve_bench import REPO, _setup as _serve_setup, _teardown


def _args(p) -> None:
    p.add_argument("--model", default="pipeedge/test-tiny-gpt2")
    p.add_argument("--partition", default="1,4,5,8")
    p.add_argument("--max-len", type=int, default=64)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--kv-pages", type=int, default=96,
                   help="page-pool size (tools/serve.py --kv-pages)")
    p.add_argument("--kv-page-size", type=int, default=8)
    p.add_argument("--disaggregate", default="off",
                   choices=["off", "local", "wire", "process"],
                   help="run the prefill fleet split (the A/B against "
                        "'off' is the docs/evidence record); 'process' "
                        "spawns REAL separate prefill worker processes "
                        "over DCN with the lease/ack ship protocol")
    p.add_argument("--prefill-ranks", type=int, default=2,
                   help="worker processes of --disaggregate process")
    p.add_argument("--fault", default="off",
                   choices=["off", "kill-prefill"],
                   help="kill-prefill (needs --disaggregate process): "
                        "run a FOURTH phase — the phase-2 decode load "
                        "while a prefill worker is SIGKILLed mid-window "
                        "— and record the fault window's decode p99, "
                        "goodput, recovery_s (respawn + readmission), "
                        "and pages leaked (the ISSUE 15 robustness A/B)")
    p.add_argument("--chunked", type=int, default=0, metavar="TOKENS",
                   help="serve with --chunked-prefill TOKENS --step-join "
                        "(iteration-level scheduling). The A/B against "
                        "0 — run-to-completion prefill, same seed — is "
                        "the continuous-batching evidence record: the "
                        "phase-4 burst decode p99 should drop while "
                        "goodput/attainment hold")
    p.add_argument("--chunked-budget", type=int, default=0,
                   metavar="TOKENS",
                   help="explicit --prefill-budget for the chunked arm "
                        "(0 = serve.py default: one chunk per tick). "
                        "Raising it past the chunk size keeps short "
                        "steady-state prompts from queueing behind a "
                        "long-prompt spike's chunk stream")
    p.add_argument("--burst-n", type=int, default=3,
                   help="phase-4 spike size (loadgen --burst long "
                        "prompts launched back-to-back mid-run)")
    p.add_argument("--qps", type=float, default=3.0,
                   help="offered rate for every phase (fixed, not "
                        "calibrated: the phases compare against each "
                        "other, so one knob keeps them comparable)")
    p.add_argument("--duration", type=float, default=6.0)
    p.add_argument("--new-tokens", type=int, default=6)
    p.add_argument("--shared-spec", default="shared:16:20:2",
                   help="phase-1 prompt distribution "
                        "(loadgen shared:PFX:TOTAL:POOL)")
    p.add_argument("--short-len", type=int, default=6,
                   help="phase-2/3 decode-load prompt length")
    p.add_argument("--long-len", type=int, default=48,
                   help="phase-3 background prefill-burst prompt length "
                        "(clamped to max_len - new_tokens)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--queue-capacity", type=int, default=32)
    p.add_argument("--max-active", type=int, default=0,
                   help="0 = executor default (page-bounded)")
    p.add_argument("--trace-out", default="bench_serve_kv_trace.json")
    p.add_argument("--postmortem-dir", default=None)
    p.add_argument("--startup-timeout", type=float, default=180.0)
    p.add_argument("--calibrate-s", type=float, default=0.0,
                   help="unused (fixed --qps); kept for arg parity")


def _setup(args) -> dict:
    # reuse the serve recipe's spawn/readiness/teardown machinery with
    # the paged-KV flags appended (one copy of the lifecycle logic)
    class _A:
        pass

    a = _A()
    for k, v in vars(args).items():
        setattr(a, k, v)
    a.overload_factor = 1.0
    if args.fault != "off" and args.disaggregate != "process":
        raise ValueError("--fault kill-prefill needs --disaggregate "
                         "process (there is no worker process to kill "
                         "otherwise)")
    extra = ["--kv-pages", str(args.kv_pages),
             "--kv-page-size", str(args.kv_page_size)]
    if args.disaggregate == "process":
        extra += ["--disaggregate", "process",
                  "--prefill-ranks", str(args.prefill_ranks),
                  "--prefill-lease-timeout", "5",
                  "--prefill-heartbeat-interval", "0.5"]
    elif args.disaggregate != "off":
        extra += ["--disaggregate", args.disaggregate]
    if args.chunked:
        extra += ["--chunked-prefill", str(args.chunked), "--step-join"]
        if args.chunked_budget:
            extra += ["--prefill-budget", str(args.chunked_budget)]
    if args.max_active:
        extra += ["--max-active", str(args.max_active)]
    a.extra_serve_args = extra
    return _serve_setup(a)


def _healthz(url: str) -> dict:
    with urllib.request.urlopen(f"{url}/healthz", timeout=30) as resp:
        return json.loads(resp.read())


def _post(gen_url: str, obj: dict, timeout: float = 120.0):
    req = urllib.request.Request(
        gen_url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _run(args, state) -> dict:
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools import loadgen

    url = state["url"]
    gen_url = f"{url}/generate"
    mix = {"interactive": 1.0}
    slo = dict(loadgen.DEFAULT_SLO_MS)

    # warmup: compile each phase's EXACT (prompt shape x page bucket)
    # programs once so phase p99s measure steady state, not XLA compiles
    # (paged decode compiles per page-count bucket, so new_tokens is
    # part of the shape). A process-mode prefill fleet compiles PER
    # WORKER: repeat each shape across the round-robin with DISTINCT
    # tokens (an identical prompt would hit the trie and never reach
    # the next worker)
    reps = (getattr(args, "prefill_ranks", 1)
            if args.disaggregate == "process" else 1)
    long_len = min(args.long_len, args.max_len - args.new_tokens - 1)
    for n, nt in {(loadgen.spec_max_len(args.shared_spec),
                   args.new_tokens),
                  (args.short_len, args.new_tokens), (long_len, 2),
                  (long_len, args.new_tokens)}:
        for rep in range(reps):
            _post(gen_url, {"ids": [[7 + rep] * n], "new_tokens": nt})

    # -- phase 1: shared-prefix burst --------------------------------
    kv0 = _healthz(url)["serving"]["kv"]
    watch = {"max_in_flight": 0, "min_tokens_free": None}
    watch_stop = threading.Event()

    def sample_admission():
        while not watch_stop.is_set():
            try:
                adm = _healthz(url)["serving"]["admission"]
                watch["max_in_flight"] = max(watch["max_in_flight"],
                                             adm["in_flight"])
                free = adm.get("tokens_free")
                if free is not None:
                    cur = watch["min_tokens_free"]
                    watch["min_tokens_free"] = (free if cur is None
                                                else min(cur, free))
            except OSError:
                pass
            watch_stop.wait(0.1)

    sampler = threading.Thread(target=sample_admission, daemon=True,
                               name="kv-admission-sampler")
    sampler.start()
    try:
        shared = loadgen.run_load(
            gen_url, args.duration, args.qps, mix=mix, slo_ms=slo,
            new_tokens=args.new_tokens, prompt_len=args.shared_spec,
            seed=args.seed, arrival="poisson")
    finally:
        watch_stop.set()
        sampler.join(timeout=30)
    kv1 = _healthz(url)["serving"]["kv"]

    # -- phase 2: decode load, no prefill pressure -------------------
    solo = loadgen.run_load(
        gen_url, args.duration, args.qps, mix=mix, slo_ms=slo,
        new_tokens=args.new_tokens, prompt_len=args.short_len,
        seed=args.seed + 1, arrival="uniform")

    # -- phase 3: same decode load + long-prompt prefill burst -------
    stop = threading.Event()
    burst_errors = [0]

    def prefill_burst():
        i = 0
        while not stop.is_set():
            try:
                _post(gen_url, {"ids": [[(i + j) % 97 for j in
                                         range(long_len)]],
                                "new_tokens": 2, "class": "batch"})
            except Exception:   # noqa: BLE001 — sheds are expected here
                burst_errors[0] += 1
            i += 1

    burster = threading.Thread(target=prefill_burst, daemon=True,
                               name="kv-prefill-burst")
    burster.start()
    try:
        contended = loadgen.run_load(
            gen_url, args.duration, args.qps, mix=mix, slo_ms=slo,
            new_tokens=args.new_tokens, prompt_len=args.short_len,
            seed=args.seed + 2, arrival="uniform")
    finally:
        stop.set()
        burster.join(timeout=120)
    kv2 = _healthz(url)["serving"]["kv"]

    # -- phase 4: decode load + seeded mid-run long-prompt SPIKE -----
    # Unlike phase 3's continuous hammering, this is loadgen's --burst:
    # N long prompts launch back-to-back at the run's midpoint, and the
    # steady-state decode latencies inside the spike's blast-radius
    # window report as burst.during_ms — the number chunked prefill
    # exists to hold down (run-to-completion prefill stalls every
    # decode step behind each long prompt pass; chunked interleaves).
    # Runs in BOTH arms so the --chunked 0 vs N records A/B cleanly.
    spike = loadgen.run_load(
        gen_url, args.duration, args.qps, mix=mix, slo_ms=slo,
        new_tokens=args.new_tokens, prompt_len=args.short_len,
        seed=args.seed + 4, arrival="uniform",
        burst={"at": 0.5, "n": args.burst_n, "len": long_len,
               "window_s": 2.0})
    sched = _healthz(url)["serving"].get("scheduler")

    # -- phase 5 (opt-in): decode load through a prefill-worker kill --
    # the robustness half of the disaggregation A/B (ISSUE 15): the
    # SAME decode load as phase 2, but a prefill worker is SIGKILLed
    # mid-window — the lease protocol must re-dispatch / fall back
    # (zero lost, zero errors), the supervisor must respawn + readmit
    # (recovery_s), and the page pool must close with zero leaks
    fault_block = None
    if args.fault == "kill-prefill":
        import os as os_mod
        import signal as signal_mod
        import threading as threading_mod
        import time as time_mod
        kv_pre = _healthz(url)["serving"]["kv"]
        workers = kv_pre["prefill"]["workers"]
        victim_rank, victim = sorted(workers.items())[0]
        t_kill = [None]
        t_readmit = [None]

        def kill_and_watch():
            # the killer thread ALSO watches for readmission, so a
            # worker that respawns mid-burst gets its true recovery
            # time — polling only after the load window would alias
            # recovery_s to the window length
            time_mod.sleep(min(1.0, args.duration / 4))
            os_mod.kill(victim["pid"], signal_mod.SIGKILL)
            t_kill[0] = time_mod.monotonic()
            deadline = t_kill[0] + args.duration + 60
            seen_down = False       # death detection lags the SIGKILL:
            while time_mod.monotonic() < deadline:   # a full live set
                try:                 # only counts as READMISSION after
                    prefill = _healthz(url)["serving"]["kv"]["prefill"]
                except OSError:      # the rank was observed gone
                    time_mod.sleep(0.3)
                    continue
                if len(prefill["live"]) < len(workers):
                    seen_down = True
                elif seen_down:
                    t_readmit[0] = time_mod.monotonic()
                    return
                time_mod.sleep(0.2)

        kt = threading_mod.Thread(target=kill_and_watch, daemon=True,
                                  name="kv-prefill-killer")
        kt.start()
        faulted = loadgen.run_load(
            gen_url, args.duration, args.qps, mix=mix, slo_ms=slo,
            new_tokens=args.new_tokens, prompt_len=args.short_len,
            seed=args.seed + 3, arrival="uniform")
        kt.join(timeout=args.duration + 90)
        recovery_s = (round(t_readmit[0] - t_kill[0], 3)
                      if t_kill[0] is not None and t_readmit[0] is not None
                      else None)
        kv_after = _healthz(url)["serving"]["kv"]
        # FAULT-WINDOW deltas, not server-lifetime cumulatives — the
        # same discipline the phase-1 prefix stats follow above:
        # leases shipped during warmup/phases 1-3 must not be
        # attributed to the fault window
        lease_delta = {
            k: kv_after["prefill"]["leases"][k]
            - kv_pre["prefill"]["leases"].get(k, 0)
            for k in kv_after["prefill"]["leases"]}
        colo_pre = kv_pre["prefill"].get("colocated") or {}
        colo_delta = {
            k: v - colo_pre.get(k, 0)
            for k, v in (kv_after["prefill"].get("colocated")
                         or {}).items()} or None
        fault_block = {
            "victim_rank": int(victim_rank),
            "decode_p99_ms": faulted["latency_ms"]["p99"],
            "goodput_rps": round(sum(
                c["goodput_rps"]
                for c in faulted["classes"].values()), 3),
            "errors": faulted["totals"]["error"],
            "lost": faulted["client_dropped"],
            "recovery_s": recovery_s,
            "readmitted": recovery_s is not None,
            "leases": lease_delta,
            "colocated": colo_delta,
            "pages_leaked": kv_after["leaked"]
            - kv_pre.get("leaked", 0),
        }

    # PHASE-1 deltas, not server-lifetime cumulatives: the warmup posts
    # (guaranteed misses) and later phases must not dilute the shared-
    # prefix phase's hit rate
    lookups = kv1["prefix"]["lookups"] - kv0["prefix"]["lookups"]
    hits = kv1["prefix"]["hits"] - kv0["prefix"]["hits"]
    hit_rate = None if lookups <= 0 else round(hits / lookups, 4)
    p99_solo = solo["latency_ms"]["p99"]
    p99_contended = contended["latency_ms"]["p99"]
    errors = (shared["totals"]["error"] + solo["totals"]["error"]
              + contended["totals"]["error"] + spike["totals"]["error"]
              + spike["burst"]["error"])
    notes = None
    if errors:
        notes = (f"{errors} handler error(s); first: "
                 f"{shared['first_error'] or solo['first_error'] or contended['first_error'] or spike['first_error'] or spike['burst']['first_error']}")
    goodput = round(sum(c["goodput_rps"]
                        for c in shared["classes"].values()), 3)
    return {
        "throughput": {"value": goodput, "unit": "req/s",
                       "detail": "shared-prefix phase goodput"},
        "latency_ms": {"p50": solo["latency_ms"]["p50"],
                       "p95": solo["latency_ms"]["p95"],
                       "p99": p99_solo, "n": solo["latency_ms"]["n"]},
        "kv": {
            "pages": args.kv_pages, "page_size": args.kv_page_size,
            "disaggregate": args.disaggregate,
            # the token-budget-vs-dense-slots claim in record form: the
            # budget's token capacity, how many max_len dense slots the
            # same memory would be, and the observed concurrency peak
            "token_budget": args.kv_pages * args.kv_page_size,
            "dense_slots_equivalent": (args.kv_pages
                                       * args.kv_page_size)
            // args.max_len,
            "max_in_flight": watch["max_in_flight"],
            "min_tokens_free": watch["min_tokens_free"],
            "prefix_hit_rate": hit_rate,
            "prefix_lookups": lookups,
            "pages_reused_total": kv1["prefix"]["pages_reused_total"],
            "pages_cached": kv1["prefix"]["pages_cached"],
            "pool_occupancy_after": kv2["pool"]["occupancy"],
            "pages_evicted_total": kv2["pool"]["pages_evicted_total"],
            "decode_p99_ms": {"solo": p99_solo,
                              "with_prefill": p99_contended},
            "decode_p99_ratio": (None if not p99_solo or not p99_contended
                                 else round(p99_contended / p99_solo, 3)),
            # the continuous-batching A/B's headline block: chunk
            # config + the spike phase's decode-under-burst latency
            # (--chunked 0 vs N, same seed — docs/SERVING.md)
            "chunked": {
                "chunk_tokens": args.chunked,
                "step_join": bool(args.chunked),
                "prefill_chunks": (None if sched is None
                                   else sched["prefill_chunks"]),
                "burst_n": args.burst_n,
                "burst_prompt_len": long_len,
                "burst_decode_p99_ms": spike["burst"]["during_ms"]["p99"],
                "burst_decode_p50_ms": spike["burst"]["during_ms"]["p50"],
                "burst_window_served": spike["burst"]["during_ms"]["n"],
                "spike_p99_ms": spike["burst"]["latency_ms"]["p99"],
                "goodput_rps": round(sum(
                    c["goodput_rps"]
                    for c in spike["classes"].values()), 3),
                "attainment": spike["classes"]["interactive"]
                ["slo_attainment"],
            },
            "fault": fault_block,
            "shed": {"shared": shared["totals"]["shed"],
                     "solo": solo["totals"]["shed"],
                     "with_prefill": contended["totals"]["shed"]},
            "errors": errors,
        },
        "notes": notes,
        "extras": {"shared": shared, "solo": solo,
                   "contended": contended, "spike": spike},
    }


def _register():
    from . import Recipe, register
    register(Recipe(
        "serve_kv", "paged-KV serving bench: shared-prefix hit rate, "
                    "page-pool occupancy, and decode p99 with/without a "
                    "concurrent prefill burst (colocated vs "
                    "--disaggregate is the docs/evidence A/B)",
        _args, _run, setup=_setup, teardown=_teardown, tier="fast"))


_register()
