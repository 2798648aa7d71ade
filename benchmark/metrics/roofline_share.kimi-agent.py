"""What the traced calls need at the chip's peaks over the trace's busy
seconds, in percent: for each traced prefill and each traced decode step
the larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(`benchmark/costs_kimi.py`: a prompt's attention expanded, a step's
absorbed over the live rows, every value at the bytes the cell stores it
in). Held experts a token and distinct experts a step come from the
program's counters as ratios (`moe_assignments / mla_rows_written`,
`moe_experts_touched / moe_layer_calls`), which the warm batch and the
traced calls do not skew. The XLA path's share: the program has no kernel
of its own."""
from benchmark import costs_kimi, prom


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
    counts = {(name, phase): _counter(text, f"pipeedge_{name}_total", phase)
              for name in ("moe_assignments", "moe_experts_touched",
                           "moe_layer_calls", "mla_rows_written")
              for phase in ("prefill", "decode")}
    if not all(counts.values()):
        return None
    config, rows = observed["config"], observed["rows"]
    layers = config["num_hidden_layers"]
    expert_layers = layers - min(config["first_k_dense_replace"], layers)

    def held_a_token(phase):    # rows_written counts a token once a layer
        return counts["moe_assignments", phase] * layers \
            / (counts["mla_rows_written", phase] * expert_layers)

    touched = counts["moe_experts_touched", "decode"] \
        / counts["moe_layer_calls", "decode"]
    generations = steps / (observed["trace_new_tokens"] - 1)
    prompt_len = observed["prompt_len"]
    live = prompt_len + observed["trace_new_tokens"] / 2.0
    peaks = observed["peaks"]
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    prefill_s = max(
        costs_kimi.prefill_flops(config, rows, prompt_len,
                                 held_a_token("prefill")) / flops,
        costs_kimi.prefill_bytes(config, rows, prompt_len) / hbm)
    step_s = max(
        costs_kimi.decode_step_flops(config, rows, live,
                                     held_a_token("decode")) / flops,
        costs_kimi.decode_step_bytes(config, rows, live, touched) / hbm)
    return 100.0 * (generations * prefill_s + steps * step_s) \
        / trace["busy_s"]
