"""Process start to the start of the window: weights, engine, warm-up
and any compilation (host clock)."""


def read(run):
    return run.setup_s
