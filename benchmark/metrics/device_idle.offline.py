"""Percent of the traced window in which no operation ran on the device
(mean over the chips used): 1 - union of device operation intervals over
the window."""
from benchmark import xplane


def read(observed):
    return xplane.idle_share(observed.get("trace"))
