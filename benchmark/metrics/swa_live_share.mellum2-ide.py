"""Percent of the positions the window layers' calls read that the window
kept, in the measured window of a served cell:
`pipeedge_swa_positions_live_total` over `pipeedge_swa_positions_read_total`,
both phases, each differenced between the scrapes before and after the window
(the server is a child of the benchmark: its registry is read over
`/metrics`, where the executor puts the device's counts at every scrape). A
window layer reads its slot's ring, the last `sliding_window` positions as
stored, and the call's own rows, and masks what lies outside each query's
window: near 100 in a step of rows past the window, `W / (W + span)` in a
span of a prompt pass. A ring read to the furthest live row's width, as the
full layers' rows are, would show a few percent."""
from benchmark import prom


def _gain(observed, name):
    after = prom.samples(observed.get("metrics_after", ""), name)
    if not after:
        return None
    before = prom.samples(observed.get("metrics_before", ""), name)
    return sum(value for _, value in after) \
        - sum(value for _, value in before)


def read(observed):
    read_positions = _gain(observed, "pipeedge_swa_positions_read_total")
    live = _gain(observed, "pipeedge_swa_positions_live_total")
    if not read_positions or live is None:
        return None
    return 100.0 * live / read_positions
