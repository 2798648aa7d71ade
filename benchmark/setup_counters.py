"""What the three `setup_*` readers share: the text they read the program's
start-up counters from, and a sum over one family's samples.

The served cell hands over the scrape it took when set-up ended
(`metrics_before`: after the warm-up requests, before the window). A cell
that runs the pipeline in its own process reads that process's registry as
it stands when the readers run; nothing is built inside the window, so what
it holds was counted in set-up (the plain reference's programs, built after
the window, are `program="other"`, which no reader counts, and the
reference reads the weights file without the program's loader)."""
from benchmark import prom


def text(observed):
    if "metrics_before" in observed:
        return observed["metrics_before"]
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return ""
    return metrics.REGISTRY.render()


def total(observed, family, keep):
    """The sum of `family`'s samples whose labels `keep` accepts; None
    where the program has no such family (a commit before it), and in the
    CPU rehearsal (no `peaks` in `observed`): what XLA's CPU client takes
    to place weights and build programs is no number of a cell."""
    if "peaks" not in observed:
        return None
    rows = prom.samples(text(observed), family)
    if not rows:
        return None
    return sum(value for labels, value in rows if keep(labels))
