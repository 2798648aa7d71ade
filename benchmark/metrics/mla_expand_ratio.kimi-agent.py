"""Latent rows the prefill expanded to their heads' keys and values over
latent rows it wrote, from the program's counters (phase prefill): 1.0 means
every prompt position's latent was expanded once, in the span that wrote it;
a prefill that expanded the cached window again for every span would read
the number of spans, half of it and more."""
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

    written = prefill("pipeedge_mla_rows_written_total")
    expanded = prefill("pipeedge_mla_rows_expanded_total")
    if not written or expanded is None:
        return None
    return expanded / written
