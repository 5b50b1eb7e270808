"""Device: share of the loaded time in the traced slice in which no
operation ran on the device while the host was inside ``serve.drain``
or ``serve.prefill_sample``, in %: the idle time that the engine's two
host syncs explain.  The program's spans, mapped onto the profiler
trace's clock; at most ``idle_share.chat``."""
from bench import spans


def read(run):
    return spans.idle_sync_share(run, spans.ring(run))
