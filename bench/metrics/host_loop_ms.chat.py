"""Scheduler: the engine's host loop per iteration, in ms: the mean, over
the window's ``serve.step`` spans that issued a decode step, of the
step's host time outside its two host syncs (``serve.drain_wait`` and
the sync of ``serve.prefill_sample``).  The program's own spans, from
the flight recorder on the host clock."""
from bench import spans


def read(run):
    return spans.host_loop_ms(run, spans.ring(run))
