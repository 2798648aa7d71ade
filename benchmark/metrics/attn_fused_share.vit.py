"""Percent of the attention cores the program traced that a kernel of `ops/`
took (`models/layers.py::self_attention` counts each where it chooses, once
a block a program traced: a stage program that scans its blocks counts one,
the host driver's unrolled one a block), of all it traced, from the
program's counter by path: 100 means every ViT block's core runs
`ops/short_attention.py` (q, k and v read as the projections wrote them, no
transposed copy and no score outside VMEM); 0 that all kept XLA's einsums.
Nothing to read where the program lacks the counter (the parent of PR 60)
or traced no core, nor off the chip (`run.result_line` gives a chip's run
its peaks): a rehearsal on the CPU keeps every core on the einsums by rule,
which says nothing of the program a chip runs."""
from benchmark import prom


def read(observed):
    if "peaks" not in observed:
        return None
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    rows = {labels.get("path"): value for labels, value in prom.samples(
        metrics.REGISTRY.render(), "pipeedge_attn_core_blocks_total")}
    fused, einsum = rows.get("fused"), rows.get("einsum")
    if fused is None or einsum is None or not fused + einsum:
        return None
    return 100.0 * fused / (fused + einsum)
