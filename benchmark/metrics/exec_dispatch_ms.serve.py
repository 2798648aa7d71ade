"""Host time of one stage-step's dispatch in the server's executor: seconds
gained by `pipeedge_span_seconds_total{cat="stage",name="exec0"}` over the
spans gained by its count, between the scrapes before and after the window.
The digest behind `/metrics` never drops a span, as the ring may.

Read only from a run on a chip (`peaks` in `observed`): in the CPU rehearsal
the same spans time XLA's CPU client, which is no number of this cell."""
from benchmark import prom


def gained(observed, family, cat, names):
    """Gain of one digest family between the two scrapes, summed over the
    spans of category `cat` named in `names`."""
    def total(text):
        return sum(value for labels, value in prom.samples(text, family)
                   if labels.get("cat") == cat and labels.get("name") in names)
    return total(observed["metrics_after"]) - total(observed["metrics_before"])


def read(observed):
    if "metrics_after" not in observed or "peaks" not in observed:
        return None
    steps = gained(observed, "pipeedge_span_count_total", "stage", {"exec0"})
    if steps <= 0:
        return None
    return gained(observed, "pipeedge_span_seconds_total", "stage",
                  {"exec0"}) / steps * 1e3
