"""Bytes the traced window's decode steps must read from HBM (every matmul
weight once a step, and each row's live cache at the batch's mean length,
from the configuration's shapes) over the chip's busy seconds times the HBM
peak, in percent. The busy seconds also hold the batches' prefills."""
from benchmark import costs


def read(observed):
    trace, steps = observed.get("trace"), observed.get("trace_decode_steps")
    if not trace or not steps:
        return None
    live = observed["prompt_len"] + observed["trace_new_tokens"] / 2.0
    needed = steps * costs.gpt2_decode_step_bytes(
        observed["config"], observed["rows"], live)
    return 100.0 * needed / (trace["busy_s"]
                             * observed["peaks"]["hbm_bytes_per_s"])
