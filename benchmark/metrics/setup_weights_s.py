"""Seconds of set-up the weight loader and the builder's placement took:
`pipeedge_startup_seconds_total`, phase `weights_read` (the weights file to
host arrays) plus phase `weights_place` (host arrays to the stage's
parameters as the builder keeps them: cast, stacking, `device_put`). The
phases exclude each other, so the sum is wall time on the building thread;
no fence is added, so what an asynchronous transfer still owes when the
builder returns is not in it."""
from benchmark import setup_counters


def read(observed):
    return setup_counters.total(
        observed, "pipeedge_startup_seconds_total",
        lambda labels: labels.get("phase") in ("weights_read",
                                               "weights_place"))
