"""The retention state kernel's share of its roofline, in percent: the bytes
the traced decode steps' calls of the in-place state kernel must move
(`ops/retention_step.py`: one layer's state read once and written once a
call, a call a layer a step; `benchmark/costs_brumby.py`) over the self time
of the device operations the trace names `retention_step` (as the profiler
prints a Mosaic kernel under the name its `pallas_call` was given), as a
share of the HBM's peak. The kernel's other operands (the sum of keys, q',
k', v, the numerators: under a hundredth of the state) are left out of the
count, so the share cannot pass 100%. Nothing to read where the trace has no
such operation among its longest (a program without the kernel, a backend
that keeps the jnp step, which moves the state three times and shows in
`roofline_share.brumby-longgen` instead)."""


def read(observed):
    trace, steps = observed.get("trace"), observed.get("trace_decode_steps")
    if not trace or not steps:
        return None
    seconds = sum(spent for name, spent in trace.get("device_ops", ())
                  if "retention_step" in name)
    if not seconds:
        return None
    try:
        from benchmark import costs_brumby as costs
    except ImportError:
        return None
    config = observed["config"]
    moved = steps * config["num_hidden_layers"] * 2 * observed["rows"] \
        * costs.layer_state_bytes_a_row(config)
    return 100.0 * moved / (seconds * observed["peaks"]["hbm_bytes_per_s"])
