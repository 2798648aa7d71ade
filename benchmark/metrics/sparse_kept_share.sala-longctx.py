"""Percent of the blocks the sparse layers' calls fetched that their queries'
selections kept, from the program's counters (`pipeedge_sparse_blocks_kept_
total` over `pipeedge_sparse_blocks_read_total`, both phases, a query a KV
head): 100 means no block was read that the selection did not keep (a gather
of a query's own blocks: what a decode step does); a span that reads the
whole window under a mask reads every block at or before the query, so the
share is what of the causal blocks the selection keeps. A traced generation
is nine tenths prefill: the share speaks for the span's read."""
from benchmark import prom


def read(observed):
    try:
        from pipeedge_tpu.telemetry import metrics
    except ImportError:
        return None
    text = metrics.REGISTRY.render()

    def total(name):
        return sum(value for _, value in prom.samples(text, name))

    fetched = total("pipeedge_sparse_blocks_read_total")
    if not fetched:
        return None
    return 100.0 * total("pipeedge_sparse_blocks_kept_total") / fetched
