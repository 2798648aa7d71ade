"""Median time to first token, from when the request was due, under
overload: the queue's length in time. Swings with the smallest change just
above capacity, which is why it is no end-to-end metric here."""


def read(observed):
    summary = observed.get("summary")
    return summary and summary.get("ttft_p50_ms")
