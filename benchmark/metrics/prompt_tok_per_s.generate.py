"""Prompt positions a second: rows x positions the steady batches' prompt
passes computed (`pipeedge_generate_positions_total{phase="prompt"}`) over
the seconds from each batch's start to its first token ready
(`pipeedge_generate_seconds_total{phase="prompt"}`): cache allocation and
every span of the prompt are in it, no step is. Nothing to read on a
program without the counters, or before a batch."""
from benchmark import generate_counters


def read(observed):
    value = generate_counters.reader()
    positions = value("pipeedge_generate_positions_total", phase="prompt")
    seconds = value("pipeedge_generate_seconds_total", phase="prompt")
    if positions is None or not seconds:
        return None
    return positions / seconds
