"""Percent of the steady batches' seconds (prompt plus decode: a batch
start to end) that were the excess of stalled intervals, host side and
device side together (`pipeedge_generate_stall_seconds_total`): says of a
line whether a stall fell in it. The batch's own account, with the stall's
place, is on the run's stderr. Nothing to read on a program without the
counters."""
from benchmark import generate_counters


def read(observed):
    value = generate_counters.reader()
    family = "pipeedge_generate_stall_seconds_total"
    host, device = value(family, side="host"), value(family, side="device")
    seconds = generate_counters.batch_seconds(value)
    if host is None or device is None or seconds is None:
        return None
    return 100.0 * (host + device) / seconds
