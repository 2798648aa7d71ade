"""Milliseconds of a decode step on the device, the prompt not in it: the
seconds from a batch's first token ready to the batch's end
(`pipeedge_generate_seconds_total{phase="decode"}`: the steps, and the tail
after the last token, which is what a batch costs beside its prompt) over
the steps taken (`pipeedge_generate_steps_total`), steady batches only. `decode_step_ms`
beside it is the window over the steps, so it charges the prompts to them.
Nothing to read on a program without the counters, or before a batch."""
from benchmark import generate_counters


def read(observed):
    value = generate_counters.reader()
    seconds = value("pipeedge_generate_seconds_total", phase="decode")
    steps = value("pipeedge_generate_steps_total")
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
