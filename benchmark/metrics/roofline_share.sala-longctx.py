"""What the traced calls need at the chip's peaks over the trace's busy
seconds, in percent: for each traced prefill and each traced decode step the
larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(`benchmark/costs_minicpm_sala.py`: a sparse layer's query over the
positions it keeps and the pooled keys it scores, a prompt's lightning
attention in the chunked form, a step's as the recurrence over the state,
every value at the bytes the cell stores it in). The XLA path's share: the
program has no kernel of its own. Nothing to read where the program lacks
the family (its counters are absent)."""
from benchmark import prom


def read(observed):
    trace, steps = observed.get("trace"), observed.get("trace_decode_steps")
    if not trace or not steps or not trace.get("busy_s"):
        return None
    try:
        from benchmark import costs_minicpm_sala as costs
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    if not list(prom.samples(metrics.REGISTRY.render(),
                             "pipeedge_lightning_positions_chunked_total")):
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
