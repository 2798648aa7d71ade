"""Seconds of set-up that went into building the pipeline's own programs:
`pipeedge_jax_program_build_seconds_total` over every `program` but `other`
(eager operations, the benchmark's draws and its reference), all four
steps: `trace`, `lower`, and `compile` or `cache_read`. The persistent
cache turns the third into the fourth and saves nothing of the first two."""
from benchmark import setup_counters


def read(observed):
    return setup_counters.total(
        observed, "pipeedge_jax_program_build_seconds_total",
        lambda labels: labels.get("program") != "other")
