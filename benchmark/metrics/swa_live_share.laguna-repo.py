"""Percent of the positions the window layers' calls read that the window
kept: `pipeedge_swa_positions_live_total` over
`pipeedge_swa_positions_read_total`, a span's and a step's together. A
window layer reads its whole ring (the last `sliding_window` positions, as
stored) and the call's own rows, and masks what lies outside each query's
window: near 100 in a step once the ring is full, `W / (W + span)` in a span.
A read that followed the ladder's width would show `W / context`, 6% at 8k."""
from benchmark import prom


def read(observed):
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    text = metrics.REGISTRY.render()

    def total(name):
        return sum(value for _, value in prom.samples(text, name))

    read_positions = total("pipeedge_swa_positions_read_total")
    if not read_positions:
        return None
    return 100.0 * total("pipeedge_swa_positions_live_total") \
        / read_positions
