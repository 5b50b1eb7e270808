"""repro.serve — serving engines over the IAAT-routed model stack.

:class:`PagedEngine` (the only production engine): paged KV cache +
per-slot recurrent state + slot-level continuous batching (mid-flight
admission, chunked prefill, device-side sampling,
preempt-on-exhaustion) for every decoder-only family.
:class:`ContinuousBatcher`: the wave-based reference, retired to
tests/benchmarks as the temperature-0 parity oracle.
"""
from repro.serve.engine import (ContinuousBatcher, PagedEngine, Request,
                                make_serve_fns, paged_step_fns, sample)
from repro.serve.paged import (BlockAllocator, BlockTable, CacheMap,
                               OutOfBlocks, SlotStateStore)
from repro.serve.sched import Seq, SlotScheduler

__all__ = [
    "ContinuousBatcher", "PagedEngine", "Request", "make_serve_fns",
    "paged_step_fns", "sample", "BlockAllocator", "BlockTable", "CacheMap", "OutOfBlocks",
    "SlotStateStore", "Seq", "SlotScheduler",
]
