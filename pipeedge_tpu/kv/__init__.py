"""The paged KV plane (ROADMAP item 2): shared paged KV pool, prompt-
prefix trie, page-table execution backend, and prefill/decode
disaggregation.

Dense per-request cache slots bound serving concurrency by SLOTS; this
package bounds it by TOKENS instead, shares prompt prefixes across
requests automatically, and lets a prefill fleet feed a decode fleet
over the existing tiered transport:

- `pool`:    `KvPagePool` — per-stage page arenas, refcounts, eviction
- `prefix`:  `PrefixTrie` — whole-page prompt matching + cold eviction
- `backend`: `PagedKvBackend` — the executor's gather/scatter cache
             provider (token-identical to the dense path for fp caches)
- `ship`:    KV rows as wire-v2 frames (int8 option, CRC, socket path)
- `disagg`:  `PrefillFleet` — prompt passes on a dedicated IN-PROCESS
             pipeline, results shipped into the decode fleet's pages
- `fleet`:   `RemotePrefillFleet`/`PrefillWorkerLoop` — the CROSS-
             PROCESS fleet (tools/prefill_worker.py ranks over DCN)
             with the fault-tolerant lease/ack ship protocol
             (docs/FAULT_TOLERANCE.md disaggregated serving)

Grounded in the Gemma-on-TPU serving comparison and production paged-
attention practice (PAPERS.md); docs/SERVING.md has the operator story
(token-budget math, brownout evict rung, knob table).
"""
from .backend import PagedKvBackend
from .disagg import PrefillFleet
from .fleet import (PrefillUnavailable, PrefillWorkerLoop,
                    RemotePrefillFleet)
from .pool import KvPagePool, PoolExhausted, pages_for
from .prefix import PrefixTrie

__all__ = [
    "KvPagePool", "PagedKvBackend", "PoolExhausted", "PrefillFleet",
    "PrefillUnavailable", "PrefillWorkerLoop", "PrefixTrie",
    "RemotePrefillFleet", "pages_for",
]
