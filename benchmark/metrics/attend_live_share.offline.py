"""Percent of the cache positions the stage programs attended that were
live: `pipeedge_attend_positions_total`, kind live over kind read, a span's
and a step's together. A call attends the static window it was compiled for
(a width off `attend_bucket`'s ladder) and masks what lies at or past its
first position to exact zeros; the rest of the window is bytes read and
products made for nothing. 100 would be a window cut to the live length at
every call, which is a program a position."""
from benchmark import prom


def read(observed):
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    text = metrics.REGISTRY.render()

    def total(kind):
        return sum(value for labels, value in prom.samples(
            text, "pipeedge_attend_positions_total")
            if labels.get("kind") == kind)

    read_positions = total("read")
    if not read_positions:
        return None
    return 100.0 * total("live") / read_positions
