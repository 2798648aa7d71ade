"""The benchmark observatory: scenario recipes behind one registry.

ROADMAP item 5 ("re-arm the headline benches; gate on goodput"): every
benchmark this repo can run is a *recipe* — a named scenario with its own
argparse surface and a `run()` that returns metric blocks — and every
recipe emits the SAME one-JSON-line trajectory record (schema.py), so
`BENCH_r0N.json` is a multi-scenario artifact and `tools/bench_report.py`
can difference any two rounds with per-metric noise bands.

Recipes (see docs/PERF.md for the catalog + flags):

- `exact`              the headline streamed pipeline bench (img/s,
                       calibrated MFU, fast-numerics + quant-collectives
                       A/Bs beside it) — bench.py's historical record
- `quant_collectives`  standalone int8/int4 ICI-collective A/B (tp >= 2)
- `spmd`               one-process SPMD pipeline via runtime.py
- `dcn`                multi-process loopback DCN pipeline fleet with a
                       merged trace (bubble % + mb latency percentiles)
- `decode`             KV-cache decode tokens/sec (bench_decode.py)
- `train`              pipeline train step img/s (tools/bench_train.py)
- `serve`              loadgen-driven goodput-first serving bench: N x
                       calibrated overload against tools/serve.py, per-
                       class goodput/SLO attainment/shed taxonomy, p99
                       cross-linked to trace exemplars; --overload-
                       factors 1,3,5 sweeps a goodput-vs-offered-load
                       curve in one record
- `serve_kv`           paged-KV serving bench (--kv-pages server):
                       shared-prefix hit rate, page-pool occupancy, and
                       decode p99 with/without a concurrent prefill
                       burst (colocated vs --disaggregate A/B)
- `autoscale`          advise-vs-auto capacity-controller A/B under one
                       seeded load ramp: time-to-scale-up, per-class
                       attainment during the ramp, decision counts

Entry point: `python bench.py --recipe NAME [recipe flags]` (the default
recipe is `exact`, keeping `python bench.py` the headline record).

Lifecycle telemetry: each run counts on
`pipeedge_bench_runs_total{recipe,status}` — the full matrix is
pre-declared at registration (PL501), so a dashboard sees every recipe's
series before its first run.
"""
from __future__ import annotations

import argparse
from typing import Callable, Dict, List, Optional

from ..telemetry import metrics as prom
from . import schema

RUN_STATUSES = ("started", "ok", "error")


class Recipe:
    """One benchmark scenario. `setup` builds state (e.g. spawns a
    server), `run` measures and returns schema.BLOCK_KEYS blocks,
    `teardown` releases the state on every path."""

    def __init__(self, name: str, help_text: str,
                 add_args: Callable[[argparse.ArgumentParser], None],
                 run: Callable, setup: Optional[Callable] = None,
                 teardown: Optional[Callable] = None,
                 tier: str = "chip"):
        self.name = name
        self.help = help_text
        self.add_args = add_args
        self.setup = setup
        self.run = run
        self.teardown = teardown
        # "fast": CPU-loopback-capable, CI bench-smoke material;
        # "chip": needs a live accelerator for a meaningful number;
        # "fleet": spawns subprocess fleets
        self.tier = tier

    def parser(self) -> argparse.ArgumentParser:
        p = argparse.ArgumentParser(prog=f"bench.py --recipe {self.name}",
                                    description=self.help)
        self.add_args(p)
        return p


_RECIPES: Dict[str, Recipe] = {}

# recipe x status run counter: declared per-recipe at registration so the
# matrix renders before any recipe ever runs (PL501)
_M_RUNS = prom.REGISTRY.counter(
    "pipeedge_bench_runs_total",
    "benchmark recipe runs by recipe and status "
    "(started / ok / error)")


def register(recipe: Recipe) -> Recipe:
    if recipe.name in _RECIPES:
        raise ValueError(f"recipe already registered: {recipe.name}")
    _RECIPES[recipe.name] = recipe
    for status in RUN_STATUSES:
        _M_RUNS.declare(recipe=recipe.name, status=status)
    return recipe


def get_recipe(name: str) -> Recipe:
    _ensure_loaded()
    try:
        return _RECIPES[name]
    except KeyError:
        raise KeyError(f"unknown recipe {name!r} (available: "
                       f"{', '.join(sorted(_RECIPES))})") from None


def list_recipes() -> List[Recipe]:
    _ensure_loaded()
    return [_RECIPES[k] for k in sorted(_RECIPES)]


_loaded = False


def _ensure_loaded() -> None:
    """Import the recipe modules exactly once (they register on import).
    Deferred so `import pipeedge_tpu.benchkit` stays light — schema
    validation and bench_report never pull jax in."""
    global _loaded  # pylint: disable=global-statement
    if _loaded:
        return
    # flag AFTER the imports succeed: a failed recipe import must
    # re-raise on the next lookup, not leave a silently partial registry
    # (sys.modules caches the modules that DID import, and register()
    # only runs at first import, so a retry never double-registers)
    from . import (autoscale_bench, fleet, headline,  # noqa: F401
                   int8_compute, offline, serve_bench,  # noqa: F401
                   serve_kv_bench)  # noqa: F401
    _loaded = True


def run_recipe(name: str, argv: Optional[List[str]] = None,
               notes: Optional[str] = None) -> dict:
    """Parse `argv` with the recipe's parser, run setup -> run ->
    teardown, and return the assembled trajectory record (NOT printed —
    the caller owns stdout)."""
    recipe = get_recipe(name)
    args = recipe.parser().parse_args(argv or [])
    config = {k: v for k, v in sorted(vars(args).items())}
    _M_RUNS.inc(recipe=name, status="started")
    state = None
    try:
        if recipe.setup is not None:
            state = recipe.setup(args)
        try:
            blocks = (recipe.run(args) if recipe.setup is None
                      else recipe.run(args, state))
        finally:
            if recipe.teardown is not None:
                recipe.teardown(state)
    except BaseException:
        _M_RUNS.inc(recipe=name, status="error")
        raise
    _M_RUNS.inc(recipe=name, status="ok")
    if notes:
        existing = blocks.get("notes")
        blocks["notes"] = notes if not existing else f"{existing} {notes}"
    return schema.make_record(name, config, blocks)
