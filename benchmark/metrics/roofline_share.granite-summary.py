"""What the traced calls need at the chip's peaks over the trace's busy
seconds, in percent: for each traced prefill and each traced decode step the
larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(`benchmark/costs_granite_hybrid.py`: a prompt's Mamba-2 layers in the
chunked form, a step's as the recurrence with the state read once and
written once at the bytes the cell stores it in, every weight once with the
tied table once, the keys and values over the live positions). The model
routes nothing, so the counts need no counter of the program: the trace and
the sizes of the traced generations are all that is read. A second read or
a copy of the state shows here as a lower share. Nothing to read without a
trace."""


def read(observed):
    trace, steps = observed.get("trace"), observed.get("trace_decode_steps")
    if not trace or not steps or not trace.get("busy_s"):
        return None
    try:
        from benchmark import costs_granite_hybrid as costs
    except ImportError:
        return None
    config, rows = observed["config"], observed["rows"]
    generations = steps / (observed["trace_new_tokens"] - 1)
    prompt_len = observed["prompt_len"]
    live = prompt_len + observed["trace_new_tokens"] / 2.0
    peaks = observed["peaks"]
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    prefill_s = max(costs.prefill_flops(config, rows, prompt_len) / flops,
                    costs.prefill_bytes(config, rows, prompt_len) / hbm)
    step_s = max(costs.decode_step_flops(config, rows, live) / flops,
                 costs.decode_step_bytes(config, rows, live) / hbm)
    return 100.0 * (generations * prefill_s + steps * step_s) \
        / trace["busy_s"]
