"""Programs the server handed to the compiler inside the window: the gain
of `pipeedge_jax_compiles_total` between the scrapes before and after it.
Every shape is warmed before the window, so this is 0 unless a change
brings a shape the warm-up does not know.

Read only from a run on a chip (`peaks` in `observed`): in the CPU rehearsal
the compiler is XLA's for the CPU, which is no number of this cell."""
from benchmark import prom

NAME = "pipeedge_jax_compiles_total"


def read(observed):
    after = prom.samples(observed.get("metrics_after", ""), NAME)
    if not after or "peaks" not in observed:
        return None
    before = prom.samples(observed["metrics_before"], NAME)
    return sum(value for _, value in after) \
        - sum(value for _, value in before)
