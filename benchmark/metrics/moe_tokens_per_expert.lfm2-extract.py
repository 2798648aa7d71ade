"""Assignments a touched expert is given in one expert layer's call of a
decode step: decode-phase `moe_assignments / moe_experts_touched` from the
program's counters. With 128 rows routed 4 of 32 it is about 16 by design:
the load a held expert sees where a deployment batches hundreds of rows
(under 1 in the other sparse cells); the expert layer's tile is padding
above it."""
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

    assignments = decode("pipeedge_moe_assignments_total")
    touched = decode("pipeedge_moe_experts_touched_total")
    if not assignments or not touched:
        return None
    return assignments / touched
