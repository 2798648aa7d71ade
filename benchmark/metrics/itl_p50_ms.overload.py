"""Median gap between a request's streamed tokens under overload: about the
number of running requests times the executor's time for one stage-step."""


def read(observed):
    summary = observed.get("summary")
    return summary and summary.get("itl_p50_ms")
