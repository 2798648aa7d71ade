"""What the measured window's calls need at the chip's peaks over the
window's busy seconds, in percent. The calls are counted by the server (a
child of the benchmark, so every count is a gain between the scrapes of
`/metrics` before and after the window): the decode steps of the rows that
step together (the expert layers' decode-phase calls over the layers), their
live rows, the positions below those rows, the experts their calls touched;
the prompt passes' spans, their positions, the cached positions below them
and the experts they touched. Each phase needs the larger of its FLOPs over
the bf16 peak and its bytes over the HBM peak (`benchmark/costs_mellum.py`:
no padded or dead row, a full layer's pair once, every value at the bytes
the cell stores it in; the two phases' maxima are of sums, which is no more
than the sum of each call's maximum). The busy seconds are the window's
seconds times the busy share of the traced second, taken under the same
load. The XLA path's and the grouped expert kernel's share together: the
step has no kernel of its own."""
from benchmark import costs_mellum, prom


def _gain(observed, name, **labels):
    def total(text):
        return sum(value for got, value in prom.samples(text, name)
                   if all(got.get(key) == want
                          for key, want in labels.items()))
    if not prom.samples(observed.get("metrics_after", ""), name):
        return None
    return total(observed["metrics_after"]) \
        - total(observed.get("metrics_before", ""))


def read(observed):
    trace, peaks = observed.get("trace"), observed.get("peaks")
    if not trace or not peaks or not trace.get("busy_s"):
        return None
    config = observed["config"]
    layers = config["num_hidden_layers"]
    counts = {
        "calls": _gain(observed, "pipeedge_moe_layer_calls_total",
                       phase="decode"),
        "rows": _gain(observed, "pipeedge_decode_step_rows_total",
                      kind="live"),
        "below": _gain(observed, "pipeedge_attend_positions_total",
                       phase="decode", kind="live"),
        "touched": _gain(observed, "pipeedge_moe_experts_touched_total",
                         phase="decode"),
        "spans": _gain(observed, "pipeedge_prompt_spans_total"),
        "positions": _gain(observed, "pipeedge_prompt_positions_total"),
        "span_below": _gain(observed, "pipeedge_attend_positions_total",
                            phase="prefill", kind="live"),
        "span_touched": _gain(observed, "pipeedge_moe_experts_touched_total",
                              phase="prefill"),
    }
    if any(value is None for value in counts.values()) \
            or not counts["calls"]:
        return None
    steps = counts["calls"] / layers
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    steps_s = max(
        costs_mellum.steps_flops(config, counts["rows"], counts["below"])
        / flops,
        costs_mellum.steps_bytes(config, steps, counts["rows"],
                                 counts["below"], counts["touched"]) / hbm)
    spans_s = max(
        costs_mellum.spans_flops(config, counts["spans"],
                                 counts["positions"], counts["span_below"])
        / flops,
        costs_mellum.spans_bytes(config, counts["spans"],
                                 counts["positions"],
                                 counts["span_touched"]) / hbm)
    busy_s = observed["window_s"] * trace["busy_s"] / trace["window_s"]
    return 100.0 * (steps_s + spans_s) / busy_s
