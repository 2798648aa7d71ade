"""What the traced calls need at the chip's peaks over the trace's busy
seconds, in percent: for each traced prefill and each traced decode step
the larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(`benchmark/costs_laguna.py`: every token through its eight experts and the
shared one and no padded row, a causal pair once in the full layers, a pair
inside the window once in the others, every value at the bytes the cell
stores it in). Distinct experts a step come from the program's counters as a
ratio (decode-phase `moe_experts_touched / moe_layer_calls`), which the warm
batch and the traced calls do not skew. The XLA path's share: the program
has no kernel of its own."""
from benchmark import costs_laguna, prom


def _counter(text, name, phase):
    rows = [value for labels, value in prom.samples(text, name)
            if labels.get("phase") == phase]
    return rows[0] if rows else None


def read(observed):
    trace, steps = observed.get("trace"), observed.get("trace_decode_steps")
    if not trace or not steps or not trace.get("busy_s"):
        return None
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    text = metrics.REGISTRY.render()
    touched = _counter(text, "pipeedge_moe_experts_touched_total", "decode")
    calls = _counter(text, "pipeedge_moe_layer_calls_total", "decode")
    if not touched or not calls or "peaks" not in observed:
        return None
    config, rows = observed["config"], observed["rows"]
    generations = steps / (observed["trace_new_tokens"] - 1)
    prompt_len = observed["prompt_len"]
    live = prompt_len + observed["trace_new_tokens"] / 2.0
    peaks = observed["peaks"]
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    prefill_s = max(
        costs_laguna.prefill_flops(config, rows, prompt_len) / flops,
        costs_laguna.prefill_bytes(config, rows, prompt_len) / hbm)
    step_s = max(
        costs_laguna.decode_step_flops(config, rows, live) / flops,
        costs_laguna.decode_step_bytes(config, rows, live, touched / calls)
        / hbm)
    return 100.0 * (generations * prefill_s + steps * step_s) \
        / trace["busy_s"]
