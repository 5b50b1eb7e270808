"""Model step: mean device milliseconds of one jit_prefill execution (one
prefill chunk) in the traced slice (profiler trace)."""
from bench import readers


def read(run):
    return readers.program_ms(run, readers.PREFILL)
