"""Percent of the prompt positions the gated short convolutions took as
spans, of all they took (spans + one at a time through the one-token form),
from the program's counters (phase prefill): 100 means no prompt position
went through the one-token form; a prefill that fell back to a step a
position would read 0 and take a stage program a position."""
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

    spanned = prefill("pipeedge_shortconv_positions_spanned_total")
    stepped = prefill("pipeedge_shortconv_positions_stepped_total")
    if spanned is None or stepped is None or not spanned + stepped:
        return None
    return 100.0 * spanned / (spanned + stepped)
