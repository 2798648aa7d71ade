"""Reading the server's `/metrics` text: just enough of the Prometheus
exposition format for counters and histogram buckets."""
import math
import re

_LINE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def samples(text, name):
    """[(labels, value)] of every sample line of metric `name`."""
    rows = []
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        match = _LINE.match(line.strip())
        if match and match.group(1) == name:
            rows.append((dict(_LABEL.findall(match.group(2) or "")),
                         float(match.group(3))))
    return rows


def histogram_delta(before, after, name):
    """Per-bucket counts of histogram `name` gained between two scrapes,
    summed over every label but `le`: [(upper bound, count)], bounds
    ascending, counts not cumulative."""
    def cumulative(text):
        totals = {}
        for labels, value in samples(text, name + "_bucket"):
            bound = math.inf if labels["le"] == "+Inf" else float(labels["le"])
            totals[bound] = totals.get(bound, 0.0) + value
        return totals
    start, end = cumulative(before), cumulative(after)
    rows, below = [], 0.0
    for bound in sorted(end):
        gained = end[bound] - start.get(bound, 0.0)
        rows.append((bound, gained - below))
        below = gained
    return rows


def histogram_quantile(buckets, q):
    """The `q` quantile (0..1) of a bucketed sample, interpolated inside the
    bucket it falls in (the last finite bound where it falls in +Inf)."""
    total = sum(count for _, count in buckets)
    if total <= 0:
        return None
    wanted, seen, lower = q * total, 0.0, 0.0
    for bound, count in buckets:
        if count and seen + count >= wanted:
            if math.isinf(bound):
                return lower
            return lower + (bound - lower) * (wanted - seen) / count
        seen += count
        if not math.isinf(bound):
            lower = bound
    return lower
