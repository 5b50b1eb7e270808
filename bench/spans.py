"""Reductions of the program's own host spans, which the span metrics
share.

``PagedEngine`` writes its loop's spans (``serve.step``,
``serve.decode_issue``, ``serve.drain`` holding ``serve.drain_wait``,
``serve.prefill_chunk`` holding ``serve.prefill_sample``, ...) into the
flight recorder ``repro.obs.TRACE`` as ``SPAN`` events: ``(t, "SPAN",
-1, -1, (name, parent[, info]), dur_us)`` with ``t`` the span's end on
``perf_counter``.  The harness resets the ring when the window opens and
nothing writes to it after the drain, so a reader takes the ring as it
stands.  A ring that dropped events fails the reading; a program whose
ring holds no span of the engine reads nothing.
"""
from __future__ import annotations

import bisect
from typing import List, NamedTuple, Optional

from bench import readers, xtrace

#: Spans inside which the engine waits on the device: the token drain
#: and the boundary-token sample of a final prefill chunk.
SYNC = ("serve.drain", "serve.prefill_sample")


class Span(NamedTuple):
    name: str
    parent: Optional[str]
    info: object
    t0: float                      # perf_counter seconds
    t1: float


def ring(run) -> list:
    """The flight recorder's events; raises where it dropped any."""
    from repro import obs
    dropped = max(run.trace_dropped, obs.TRACE.dropped)
    if dropped:
        raise xtrace.TraceError(f"the flight recorder dropped {dropped} "
                                "events")
    return obs.TRACE.snapshot()


def spans(events) -> List[Span]:
    """The ``SPAN`` events, by start."""
    out = [Span(a[0], a[1], a[2] if len(a) > 2 else None, t - d * 1e-6, t)
           for t, kind, _rid, _slot, a, d in events if kind == "SPAN"]
    return sorted(out, key=lambda s: s.t0)


def host_loop_ms(run, events) -> Optional[float]:
    """Mean, over the window's ``serve.step`` spans that hold a
    ``serve.decode_issue``, of the step's host milliseconds outside its
    ``serve.drain_wait`` spans and the host sync of its
    ``serve.prefill_sample`` (that span's info, in microseconds)."""
    sp = spans(events)
    lo, hi = run.t0, run.t0 + run.seconds
    steps = [s for s in sp if s.name == "serve.step" and lo <= s.t1 < hi]
    starts = [s.t0 for s in steps]
    decode = [False] * len(steps)
    wait = [0.0] * len(steps)
    for s in sp:
        if s.name not in ("serve.decode_issue", "serve.drain_wait",
                          "serve.prefill_sample"):
            continue
        i = bisect.bisect_right(starts, s.t0) - 1
        if i < 0 or s.t1 > steps[i].t1:
            continue                    # not inside a step of the window
        if s.name == "serve.decode_issue":
            decode[i] = True
        elif s.name == "serve.drain_wait":
            wait[i] += s.t1 - s.t0
        else:
            wait[i] += s.info * 1e-6
    own = [st.t1 - st.t0 - w for st, d, w in zip(steps, decode, wait) if d]
    return sum(own) / len(own) * 1e3 if own else None


def idle_sync_share(run, events) -> Optional[float]:
    """Share (%) of the loaded traced slice (``readers.loaded_spans``) in
    which no device operation ran while the host was inside one of
    :data:`SYNC`, the spans mapped onto the trace's clock through
    ``run.to_trace_ns``."""
    sync = xtrace.union([(run.to_trace_ns(s.t0), run.to_trace_ns(s.t1))
                         for s in spans(events) if s.name in SYNC])
    loaded = readers.loaded_spans(run)
    whole = xtrace.total(loaded)
    if not sync or not whole:
        return None
    inside = [iv for lo, hi in loaded for iv in xtrace.clip(sync, lo, hi)]
    idle = xtrace.total(inside) - xtrace.total(xtrace.busy(run.trace, inside))
    return 100.0 * idle / whole
