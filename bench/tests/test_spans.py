"""The span metrics on a synthetic flight-recorder ring and a synthetic
device trace: one second of a window, with known idle gaps and host
spans."""
import pytest

from bench import harness, spans, xtrace

MS = 1e-3
T0 = 10.0                               # window start, perf_counter s
NS0 = 5e9                               # the same instant on the trace


def _ns(t):
    return NS0 + (t - T0) * 1e9


def _span(name, parent, a, b, info=None):
    arg = (name, parent) if info is None else (name, parent, info)
    return (T0 + b * MS, "SPAN", -1, -1, arg, (b - a) * MS * 1e6)


#: Spans in ms from the window's start.  Steps 0 and 100 issue a decode
#: step; step 500 only prefills; the last step ends after the window.
SPANS = [
    _span("serve.decode_issue", "serve.step", 10, 20, (2, 40)),
    _span("serve.drain_wait", "serve.drain", 50, 80),
    _span("serve.drain", "serve.step", 50, 90),
    _span("serve.step", None, 0, 100),
    _span("serve.decode_issue", "serve.step", 280, 290, (3, 70)),
    _span("serve.prefill_sample", "serve.prefill_chunk", 300, 360, 50e3),
    _span("serve.prefill_chunk", "serve.step", 290, 390),
    _span("serve.step", None, 280, 400),
    _span("serve.prefill_chunk", "serve.step", 500, 540),
    _span("serve.step", None, 500, 550),
    _span("serve.drain_wait", "serve.drain", 620, 650),
    _span("serve.drain", None, 620, 660),
    _span("serve.decode_issue", "serve.step", 1200, 1210, (1, 9)),
    _span("serve.step", None, 1200, 1300),
]

#: Device busy except 300-350 ms and 600-700 ms: idle 15 % of the second.
OPS = [("op", _ns(T0 + a * MS), _ns(T0 + b * MS))
       for a, b in ((0, 300), (350, 600), (700, 1000))]


@pytest.fixture
def ring():
    from repro import obs
    obs.set_enabled(True)
    obs.TRACE.reset()
    yield obs.TRACE
    obs.TRACE.reset()


def _run(dropped=0):
    run = harness.Run(cell=None, seconds=1.0, seed=0)
    run.t0 = T0
    run.loaded = [(T0, T0 + 1.0)]
    run.trace = xtrace.Reduced(window=(_ns(T0), _ns(T0 + 1.0)), modules=[],
                               ops=OPS, host=[])
    run.clock = (NS0, T0)
    run.trace_dropped = dropped
    return run


def _metric(name):
    return harness.load_module(harness.ROOT / "bench" / "metrics"
                               / f"{name}.py").read


def _fill(log, events):
    for t, kind, rid, slot, arg, dur in events:
        log.emit(kind, rid, slot, arg, dur, t=t)


def test_host_loop_ms(ring):
    _fill(ring, SPANS)
    # step 0: 100 ms less its 30 ms drain wait; step 280: 120 ms less the
    # 50 ms sync of its boundary sample (not the whole 60 ms span)
    assert _metric("host_loop_ms.chat")(_run()) == pytest.approx(70.0)


def test_idle_share_sync(ring):
    _fill(ring, SPANS)
    run = _run()
    sync = _metric("idle_share.sync.chat")(run)
    # idle inside the sample (300-350) and the lone drain (620-660); the
    # first drain ran while the device was busy
    assert sync == pytest.approx(9.0)
    whole = _metric("idle_share.chat")(run)
    assert whole == pytest.approx(15.0)
    assert sync <= whole


def test_readers_fail_where_the_ring_dropped_events(ring):
    _fill(ring, SPANS)
    for name in ("host_loop_ms.chat", "idle_share.sync.chat"):
        with pytest.raises(xtrace.TraceError, match="dropped 3"):
            _metric(name)(_run(dropped=3))


def test_readers_find_nothing_without_engine_spans(ring):
    ring.emit("ADMIT", 1, 0, t=T0 + 0.1)
    ring.emit("SPAN", arg=("tune.class", None), dur_us=5.0, t=T0 + 0.2)
    assert _metric("host_loop_ms.chat")(_run()) is None
    assert _metric("idle_share.sync.chat")(_run()) is None


def test_spans_by_start():
    sp = spans.spans(SPANS)
    assert [s.t0 for s in sp] == sorted(s.t0 for s in sp)
    first = sp[0]
    assert (first.name, first.parent, first.info) == ("serve.step", None,
                                                      None)
    assert first.t1 - first.t0 == pytest.approx(0.1)
