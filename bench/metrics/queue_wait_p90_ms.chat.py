"""Scheduler: 90th percentile, over the window's requests, of the wait
from scheduled arrival to first admission into a slot, in ms (the
program's ADMIT stamps on the host clock)."""
from bench import readers


def read(run):
    return readers.p90_ms(readers.queue_wait_s(run))
