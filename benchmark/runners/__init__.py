"""One module per kind of runner; a traffic file names its kind under
`runner`, and `benchmark/run.py` imports `benchmark.runners.<kind>`. Each
exports `run(ctx) -> Outcome`."""
