"""Wait from a request's arrival at the server to its execution slot: the
95th percentile of `pipeedge_admission_latency_seconds`, differenced between
the scrapes before and after the window, interpolated inside its bucket."""
from benchmark import prom

NAME = "pipeedge_admission_latency_seconds"


def read(observed):
    if "metrics_after" not in observed:
        return None
    buckets = prom.histogram_delta(observed["metrics_before"],
                                   observed["metrics_after"], NAME)
    seconds = prom.histogram_quantile(buckets, 0.95)
    return None if seconds is None else seconds * 1e3
