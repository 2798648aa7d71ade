"""What the traced calls need at the chip's peaks over the trace's busy
seconds, in percent: for each traced prefill and each traced decode step the
larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(`benchmark/costs_nemotron_h.py`: a prompt's Mamba-2 layers in the chunked
form, a step's as the recurrence with the state read once and written once
at the bytes the cell stores it in, the attention over the live positions,
the latent experts as routed). Held experts a token and distinct experts a
step come from the program's counters as ratios (`moe_assignments` over the
positions the Mamba-2 layers counted, `moe_experts_touched /
moe_layer_calls`), which the warm batch and the traced calls do not skew.
The XLA path's share: the program has no kernel of its own for the state, so
a second read or a copy of it shows here as a lower share. Nothing to read
where the program lacks the family (its counters are absent)."""
from benchmark import prom

_NAMES = ("moe_assignments", "moe_experts_touched", "moe_layer_calls",
          "ssm_positions_chunked", "ssm_positions_stepped")


def _counter(text, name, phase):
    rows = [value for labels, value in prom.samples(text, name)
            if labels.get("phase") == phase]
    return rows[0] if rows else None


def read(observed):
    trace, steps = observed.get("trace"), observed.get("trace_decode_steps")
    if not trace or not steps or not trace.get("busy_s"):
        return None
    try:
        from benchmark import costs_nemotron_h as costs
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    text = metrics.REGISTRY.render()
    counts = {(name, phase): _counter(text, f"pipeedge_{name}_total", phase)
              for name in _NAMES for phase in ("prefill", "decode")}
    if any(value is None for value in counts.values()) \
            or not counts["moe_layer_calls", "decode"]:
        return None
    config, rows = observed["config"], observed["rows"]
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]

    def held_a_token(phase):    # a Mamba-2 layer counts a token once
        tokens = (counts["ssm_positions_chunked", phase]
                  + counts["ssm_positions_stepped", phase]) \
            / pattern.count("M")
        return counts["moe_assignments", phase] \
            / (tokens * pattern.count("E"))

    touched = counts["moe_experts_touched", "decode"] \
        / counts["moe_layer_calls", "decode"]
    generations = steps / (observed["trace_new_tokens"] - 1)
    prompt_len = observed["prompt_len"]
    live = prompt_len + observed["trace_new_tokens"] / 2.0
    peaks = observed["peaks"]
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    prefill_s = max(
        costs.prefill_flops(config, rows, prompt_len,
                            held_a_token("prefill")) / flops,
        costs.prefill_bytes(config, rows, prompt_len) / hbm)
    step_s = max(
        costs.decode_step_flops(config, rows, live,
                                held_a_token("decode")) / flops,
        costs.decode_step_bytes(config, rows, live, touched) / hbm)
    return 100.0 * (generations * prefill_s + steps * step_s) \
        / trace["busy_s"]
