"""FLOPs the traced window's images need (from the configuration's shapes,
2 x MAC) over the chips' busy seconds times the bf16 peak, in percent."""
from benchmark import costs


def read(observed):
    trace, images = observed.get("trace"), observed.get("trace_images")
    if not trace or not images:
        return None
    needed = images * costs.vit_forward_flops(observed["config"])
    busy = trace["busy_s"] * len(trace["busy_s_per_chip"])
    return 100.0 * needed / (busy * observed["peaks"]["bf16_flops_per_s"])
