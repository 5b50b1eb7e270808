"""repro.serve — the serving engine over the IAAT-routed model stack.

:class:`PagedEngine`: paged KV cache + per-slot recurrent state +
slot-level continuous batching (mid-flight admission, chunked prefill,
device-side sampling, preempt-on-exhaustion) for every decoder-only
family.  :func:`paged_step_fns` returns its two device steps unjitted,
for compile checks.
"""
from repro.serve.engine import PagedEngine, Request, paged_step_fns, sample
from repro.serve.paged import (BlockAllocator, BlockTable, CacheMap,
                               OutOfBlocks, SlotStateStore)
from repro.serve.sched import Seq, SlotScheduler

__all__ = [
    "PagedEngine", "Request", "paged_step_fns", "sample", "BlockAllocator",
    "BlockTable", "CacheMap", "OutOfBlocks", "SlotStateStore", "Seq",
    "SlotScheduler",
]
