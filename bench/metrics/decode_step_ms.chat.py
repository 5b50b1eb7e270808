"""Model step: mean device milliseconds of one jit_decode execution in the
traced slice (profiler trace)."""
from bench import readers


def read(run):
    return readers.program_ms(run, readers.DECODE)
