"""Reductions the metric files share: device time per program, kernel
events, loaded spans, and the host-side request stamps.

A metric file (``bench/metrics/<name>.py``) defines ``read(run)`` and
returns its number, or None where it finds nothing to read (the harness
then fails the run: every metric it reads is listed for the cell).  A
name the reduction needs and does not find in the trace raises.
"""
from __future__ import annotations

import re
from typing import List, Optional

from bench import xtrace
from bench.harness import percentile

#: Device programs, as the profiler names the engine's jitted steps.
DECODE, PREFILL = "jit_decode", "jit_prefill"
#: Device operations of the IAAT GEMM kernels.  The profiler names a
#: device operation by its HLO text; a Pallas kernel is a custom call to
#: ``tpu_custom_call`` (its ``pallas_call`` carries no name yet), and on
#: the serving path the Router's GEMM plans are the only Pallas kernels.
KERNEL_RE = re.compile(r'custom_call_target="tpu_custom_call"')


def programs(run, name: str) -> List[xtrace.Interval]:
    """Executions of one jitted program that start inside the traced
    slice."""
    lo, hi = run.trace.window
    ev = [m for m in run.trace.module_intervals(name) if lo <= m[1] < hi]
    return ev


def program_ms(run, name: str) -> float:
    """Mean device milliseconds per execution of ``name``."""
    ev = programs(run, name)
    if not ev:
        names = sorted({m[0] for m in run.trace.modules})[:20]
        raise xtrace.TraceError(f"no {name} program in the traced slice;"
                                f" programs seen: {names}")
    return sum(b - a for _n, a, b in ev) / len(ev) * 1e-6


def kernel_events(run, within: str) -> List[xtrace.Interval]:
    """IAAT GEMM kernel operations inside executions of ``within``."""
    ops = [o for o in run.trace.ops if KERNEL_RE.search(o[0])]
    return xtrace.inside(ops, programs(run, within))


def loaded_spans(run) -> List[tuple]:
    """The traced slice where at least one request held a slot, on the
    trace's clock."""
    lo, hi = run.trace.window
    spans = [(run.to_trace_ns(a), run.to_trace_ns(b)) for a, b in run.loaded]
    return xtrace.clip(spans, lo, hi)


def issues_in_slice(run) -> List[tuple]:
    """(t, live slots, context sum) of the decode steps issued in the
    traced slice."""
    lo, hi = run.trace.window
    return [d for d in run.decode_issues
            if lo <= run.to_trace_ns(d[0]) < hi]


def queue_wait_s(run) -> List[float]:
    """Per request of the window: its first admission into a slot (the
    program's ADMIT stamp, ``repro.obs.TRACE``) minus its scheduled
    arrival."""
    if run.trace_dropped:
        raise xtrace.TraceError(f"the flight recorder dropped "
                                f"{run.trace_dropped} events")
    reqs = run.window_reqs()
    missing = [r.rid for r in reqs if r.rid not in run.admit]
    if missing:
        raise xtrace.TraceError(f"no ADMIT stamp for requests {missing[:10]}")
    return [run.admit[r.rid] - r.arrival for r in reqs]


def tpot_s(run) -> List[float]:
    return [(r.finish - r.first) / (r.n_out - 1) for r in run.window_reqs()
            if r.finish is not None and r.n_out > 1]


def p90_ms(values) -> Optional[float]:
    v = percentile(values, 90)
    return None if v is None else v * 1e3
