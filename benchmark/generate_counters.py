"""The program's account of its `DecodePipeline.generate` batches, as the
`*.generate` readers take it: the `pipeedge_generate_*` counters of the
process's registry (`pipeedge_tpu/telemetry/generate_account.py`), which
hold steady batches only: none that built a program, none under a profiler
session. So in a traced run they are the window's whole batches, and every
reader is a rate or a share of them."""
from benchmark import prom


def reader():
    """value(family, **labels) -> the sample's value, None where the
    program has no such sample (the parent of PR 49 has none of them)."""
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return lambda family, **labels: None
    text = metrics.REGISTRY.render()

    def value(family, **labels):
        rows = [value for found, value in prom.samples(text, family)
                if found == labels]
        return rows[0] if rows else None
    return value


def batch_seconds(value):
    """Seconds of the steady batches, start to end: prompt (up to the
    first token ready) plus decode (from there on); None where either is
    missing or nothing was counted."""
    family = "pipeedge_generate_seconds_total"
    prompt, decode = value(family, phase="prompt"), value(family,
                                                          phase="decode")
    if prompt is None or decode is None or prompt + decode <= 0:
        return None
    return prompt + decode
