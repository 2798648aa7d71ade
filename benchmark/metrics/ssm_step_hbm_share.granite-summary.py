"""The state kernel's share of its roofline, in percent: the bytes the
traced decode steps' calls of the in-place Mamba-2 state kernel must move
(`ops/ssm_step.py`: one layer's state read once and written once a call, a
call a Mamba-2 layer a step; `benchmark/costs_granite_hybrid.py`) over the
self time of the device operations the trace names `ssm_step`
(`jit_decode_step/_ssm_step_f32_36_64_64_64_128_`, as the profiler prints a
Mosaic kernel), as a share of the HBM's peak: what the kernel reaches at ONE
group of 64 heads, a grid cell a row of the batch. The kernel's other
operands (`dt x`, B, C, `y`: under a hundredth of the state) are left out of
the count, so the share cannot pass 100%. Nothing to read where the trace
has no such operation among its longest (a program without the kernel, a
backend that keeps the jnp step)."""


def read(observed):
    trace, steps = observed.get("trace"), observed.get("trace_decode_steps")
    if not trace or not steps:
        return None
    seconds = sum(spent for name, spent in trace.get("device_ops", ())
                  if "ssm_step" in name)
    if not seconds:
        return None
    try:
        from benchmark import costs_granite_hybrid as costs
    except ImportError:
        return None
    config = observed["config"]
    layers = config["layer_types"][:config["num_hidden_layers"]].count(
        "mamba")
    moved = steps * layers * 2 * observed["rows"] \
        * costs.layer_state_bytes_a_row(config)
    return 100.0 * moved / (seconds * observed["peaks"]["hbm_bytes_per_s"])
