"""What the traced calls need at the chip's peaks (their prefills' FLOPs over
the bf16 peak, plus their decode steps' bytes over the HBM peak) over the
trace's busy seconds, in percent. Attention is counted over the kept keys
and expert weights over the mean number of distinct experts a step touched
(`experts_touched / layer_calls`, phase decode, from the program's counters:
ratios, which the warm batch and the traced calls do not skew). The XLA
path's share: the program has no kernel of its own."""
from benchmark import costs_keye, prom


def _counter(text, name, phase):
    rows = [value for labels, value in prom.samples(text, name)
            if labels.get("phase") == phase]
    return rows[0] if rows else None


def read(observed):
    trace, steps = observed.get("trace"), observed.get("trace_decode_steps")
    if not trace or not steps:
        return None
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    text = metrics.REGISTRY.render()
    touched = _counter(text, "pipeedge_moe_experts_touched_total", "decode")
    calls = _counter(text, "pipeedge_moe_layer_calls_total", "decode")
    if not touched or not calls:
        return None
    config, rows = observed["config"], observed["rows"]
    generations = steps / (observed["trace_new_tokens"] - 1)
    live = observed["prompt_len"] + observed["trace_new_tokens"] / 2.0
    peaks = observed["peaks"]
    needed_s = generations * costs_keye.prefill_flops(
        config, rows, observed["prompt_len"]) / peaks["bf16_flops_per_s"] \
        + steps * costs_keye.decode_step_bytes(
            config, rows, live, touched / calls) / peaks["hbm_bytes_per_s"]
    return 100.0 * needed_s / trace["busy_s"]
