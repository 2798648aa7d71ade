"""Percent of the device's idle time in the traced window that the program
names: 1 - the seconds the reduction leaves under "(between host events)" and
"(gaps beyond the longest 2000)" over the window's idle seconds. The rest
lies under a span of the program (`exec/...`, `stage/exec0`, `serve/...`) or
one of JAX's own host events."""
from benchmark import xplane


def read(observed):
    trace = observed.get("trace")
    if not trace:
        return None
    idle = trace["window_s"] - trace["busy_s"]
    if idle <= 0:
        return None
    unnamed = sum(seconds for name, seconds in trace["idle_gaps"]
                  if name in (xplane.NO_HOST_EVENT, xplane.SHORT_GAPS))
    return 100.0 * (1.0 - unnamed / idle)
