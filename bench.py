"""Benchmark observatory CLI: run one scenario recipe, print ONE JSON line.

`bench.py` is now a thin dispatcher over the `pipeedge_tpu/benchkit/`
recipe registry (docs/PERF.md has the catalog and the trajectory-record
schema). The default recipe is `exact` — the historical ViT-Large
headline — so a bare `python bench.py` still produces the BENCH record
it always did (same keys, now inside the schema-versioned envelope every
recipe shares: scenario, config fingerprint, environment stamp,
noise-banded throughput block).

Usage:
    python bench.py                        # the exact headline (ViT-L b8)
    python bench.py --recipe serve         # goodput bench at 3x overload
    python bench.py --recipe quant_collectives --model ... --ubatches 8
    python bench.py --list-recipes
    python bench.py --recipe exact -- --help        # recipe flags
    python bench.py --recipe serve --append-record BENCH_r06.json

`--append-record FILE` additionally folds the record into a
multi-scenario artifact (one record per scenario, newest wins) — how a
BENCH_r0N.json re-arms per PR. `tools/bench_report.py` diffs two such
artifacts (or single records) with per-metric noise bands and gates CI.
"""
import argparse
import json
import sys


def main() -> int:
    from pipeedge_tpu import benchkit
    from pipeedge_tpu.benchkit import schema
    from pipeedge_tpu.utils import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser(
        description=__doc__.splitlines()[0], add_help=False)
    p.add_argument("-h", "--help", action="store_true")
    p.add_argument("--recipe", default="exact",
                   help="scenario recipe to run (--list-recipes)")
    p.add_argument("--list-recipes", action="store_true",
                   help="print the recipe catalog and exit")
    p.add_argument("--append-record", metavar="FILE", default=None,
                   help="also fold the record into the multi-scenario "
                        "artifact at FILE (created when missing)")
    p.add_argument("--notes", default=None,
                   help="free-form provenance appended to the record's "
                        "notes field (e.g. the r05->r06 gap note)")
    p.add_argument("--scenario-suffix", metavar="TAG", default=None,
                   help="record the run as scenario RECIPE@TAG so A/B "
                        "arms of one recipe coexist in a multi-scenario "
                        "artifact (artifact_append keys on scenario — "
                        "without a suffix the second arm replaces the "
                        "first)")
    args, rest = p.parse_known_args()
    if rest and rest[0] == "--":
        rest = rest[1:]         # `bench.py --recipe X -- <recipe flags>`

    if args.list_recipes:
        for recipe in benchkit.list_recipes():
            print(f"{recipe.name:18s} [{recipe.tier:5s}] {recipe.help}")
        return 0
    if args.help:
        recipe_given = any(a == "--recipe" or a.startswith("--recipe=")
                           for a in sys.argv[1:])
        if recipe_given:
            rest = ["--help"]   # delegate to the recipe's own parser
        else:
            p.print_help()
            return 0

    record = benchkit.run_recipe(args.recipe, rest, notes=args.notes)
    if args.scenario_suffix:
        record["scenario"] = f"{args.recipe}@{args.scenario_suffix}"
    problems = schema.validate_record(record)
    if problems:
        # a recipe that emits an invalid record is a bug, not a bench
        # result — fail loudly instead of committing a corrupt line
        print(f"bench.py: invalid record: {problems}", file=sys.stderr)
        return 2
    if args.append_record:
        schema.artifact_append(args.append_record, record)
    print(json.dumps(record, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
