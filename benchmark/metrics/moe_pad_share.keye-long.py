"""Percent of the rows the expert products multiplied that held no
assignment: 1 - assignments / rows computed, prefill and decode together,
from the program's counters (the expert layer covers each expert's group of
tokens with whole tiles, and a group's last tile runs past its end)."""
from benchmark import prom


def read(observed):
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    text = metrics.REGISTRY.render()

    def total(name):
        return sum(value for _, value in prom.samples(text, name))

    rows = total("pipeedge_moe_rows_computed_total")
    if not rows:
        return None
    return 100.0 * (1.0 - total("pipeedge_moe_assignments_total") / rows)
