"""Percent of the prompt positions the power-retention layers took through
the chunked form, of all they took (chunked + one at a time through the
recurrence), from the program's counters (phase prefill): 100 means no
prompt position was stepped one at a time; a prefill that fell back to the
recurrence a position would read 0 and take a program call a position.
Nothing to read where the program lacks the family (its counters are
absent)."""
from benchmark import prom


def read(observed):
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    text = metrics.REGISTRY.render()

    def prefill(name):
        rows = [value for labels, value in prom.samples(text, name)
                if labels.get("phase") == "prefill"]
        return rows[0] if rows else None

    chunked = prefill("pipeedge_retention_positions_chunked_total")
    stepped = prefill("pipeedge_retention_positions_stepped_total")
    if chunked is None or stepped is None or not chunked + stepped:
        return None
    return 100.0 * chunked / (chunked + stepped)
