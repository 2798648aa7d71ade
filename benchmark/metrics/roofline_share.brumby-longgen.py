"""What the traced calls need at the chip's peaks over the trace's busy
seconds, in percent: for each traced prefill and each traced decode step the
larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(`benchmark/costs_brumby.py`: a prompt's retention layers in the chunked
form over the 8,256 distinct products of a key, a step's as the recurrence
with the state read once and written once at the bytes the leaf stores,
every weight once with the head's table; no key or value is kept, so
nothing grows with the position). The model routes nothing, so the counts
need no counter of the program: the trace and the sizes of the traced
generations are all that is read. A second read or a copy of the state
shows here as a lower share. Nothing to read without a trace."""


def read(observed):
    trace, steps = observed.get("trace"), observed.get("trace_decode_steps")
    if not trace or not steps or not trace.get("busy_s"):
        return None
    try:
        from benchmark import costs_brumby as costs
    except ImportError:
        return None
    config, rows = observed["config"], observed["rows"]
    generations = steps / (observed["trace_new_tokens"] - 1)
    prompt_len = observed["prompt_len"]
    peaks = observed["peaks"]
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    prefill_s = max(costs.prefill_flops(config, rows, prompt_len) / flops,
                    costs.prefill_bytes(config, rows) / hbm)
    step_s = max(costs.decode_step_flops(config, rows) / flops,
                 costs.decode_step_bytes(config, rows) / hbm)
    return 100.0 * (generations * prefill_s + steps * step_s) \
        / trace["busy_s"]
