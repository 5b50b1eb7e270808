"""Device: share of the loaded time in the traced slice (at least one
request holding a slot) in which no operation ran on the device, in %."""
from bench import readers, xtrace


def read(run):
    spans = readers.loaded_spans(run)
    loaded = xtrace.total(spans)
    if not loaded:
        return None
    return 100.0 * (1.0 - xtrace.total(xtrace.busy(run.trace, spans)) / loaded)
