"""90th percentile, over the window's finished requests, of (finish -
first token) / (output tokens - 1) (host clock)."""
from bench import readers


def read(run):
    return readers.p90_ms(readers.tpot_s(run))
