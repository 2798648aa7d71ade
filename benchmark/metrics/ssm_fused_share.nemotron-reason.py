"""Percent of the positions the Mamba-2 layers stepped one at a time whose
state the in-place kernel moved on (`ops/ssm_step.py`: one read and one
write of the layer's state where it lies in the cache), of all they stepped,
from the program's counters (phase decode): 100 means every decode step of
every Mamba-2 layer took the kernel; 0 that all went through the jnp step
and the driver's update (a backend without Mosaic, a leaf the driver does
not write in place). Nothing to read where the program lacks either counter
(the parent of PR 48 has no `ssm_steps_fused`) or stepped no position."""
from benchmark import prom


def read(observed):
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    text = metrics.REGISTRY.render()

    def decode(name):
        rows = [value for labels, value in prom.samples(text, name)
                if labels.get("phase") == "decode"]
        return rows[0] if rows else None

    fused = decode("pipeedge_ssm_steps_fused_total")
    stepped = decode("pipeedge_ssm_positions_stepped_total")
    if fused is None or not stepped:
        return None
    return 100.0 * fused / stepped
