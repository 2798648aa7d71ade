"""Percent of a batch's seconds the generating thread was on a CPU:
`pipeedge_generate_host_cpu_seconds_total` over the seconds of the steady
batches, start to end (prompt plus decode). A dispatch may block inside
the runtime once enough programs are in flight, so the wall seconds inside
the `generate/step` spans do not say the host was working; CPU seconds do.
Near 100 the cell is bound by its host. Nothing to read on a program
without the counters."""
from benchmark import generate_counters


def read(observed):
    value = generate_counters.reader()
    cpu = value("pipeedge_generate_host_cpu_seconds_total")
    seconds = generate_counters.batch_seconds(value)
    if cpu is None or seconds is None:
        return None
    return 100.0 * cpu / seconds
