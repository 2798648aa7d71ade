"""How late the load generator sent its requests: sent minus due, 95th
percentile, on the generator's own clock. A starved generator must not read
as a fast server."""


def read(observed):
    summary = observed.get("summary")
    return summary and summary.get("gen_late_p95_ms")
