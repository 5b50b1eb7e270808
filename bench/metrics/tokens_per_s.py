"""Output tokens delivered to the client inside the window, over the
window's seconds (host clock)."""


def read(run):
    return run.tokens_in_window / run.seconds
