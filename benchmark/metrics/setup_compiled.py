"""Programs of the pipeline's own that set-up had to compile:
`pipeedge_jax_program_builds_total`, step `compile`, every `program` but
`other`. 0 in a run whose programs the persistent cache held, which is
what a cell's second run on a machine should read; in a first run, the
number of programs the cell runs."""
from benchmark import setup_counters


def read(observed):
    return setup_counters.total(
        observed, "pipeedge_jax_program_builds_total",
        lambda labels: labels.get("step") == "compile"
        and labels.get("program") != "other")
