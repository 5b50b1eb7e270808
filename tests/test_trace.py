"""repro.obs.trace: flight-recorder ring semantics, the per-request
reducer, the Perfetto/Chrome-trace export, file + CLI round-trips, and
a live paged-engine integration pass under forced preemption."""
import json
import threading

import jax
import numpy as np
import pytest

from repro import configs, obs
from repro.models import registry
from repro.models.common import XLA
from repro.obs import trace
from repro.serve import PagedEngine, Request

KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.set_enabled(True)
    obs.reset()
    yield
    obs.set_enabled(True)
    obs.reset()


# --------------------------------------------------------------------------
# EventLog ring semantics.
# --------------------------------------------------------------------------

def test_ring_drops_oldest_and_counts_drops():
    log = trace.EventLog(capacity=4)
    for i in range(10):
        log.emit("SPAN", arg=i)
    assert len(log) == 4
    assert log.n_total == 10 and log.dropped == 6
    # the ring keeps the most recent window, oldest-first
    assert [e[4] for e in log.snapshot()] == [6, 7, 8, 9]


def test_ring_reset_and_disable():
    log = trace.EventLog(capacity=8)
    log.emit("FINISH", rid=1, slot=0, arg=5)
    log.reset()
    assert len(log) == 0 and log.n_total == 0 and log.dropped == 0
    log.set_enabled(False)
    log.emit("FINISH", rid=1)
    assert len(log) == 0                 # disabled emit is a no-op
    log.set_enabled(True)
    log.emit("FINISH", rid=1)
    assert len(log) == 1


def test_ring_rejects_unknown_event_and_bad_capacity():
    log = trace.EventLog(capacity=4)
    with pytest.raises(ValueError, match="unknown trace event"):
        log.emit("NOT_AN_EVENT")
    with pytest.raises(ValueError):
        trace.EventLog(capacity=0)


def test_ring_capacity_from_env(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE_CAP", "7")
    assert trace.EventLog().capacity == 7


def test_ring_threaded_emits_never_corrupt():
    """Concurrent emitters + a reader snapshotting mid-stream: the ring
    never raises, snapshots are always well-formed, and the derived
    dropped count stays consistent with what survived."""
    log = trace.EventLog(capacity=256)
    n, nthreads = 2000, 4
    errors = []

    def work():
        try:
            for _ in range(n):
                log.emit("SPAN")
        except Exception as e:           # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(nthreads)]
    for t in threads:
        t.start()
    snaps = [log.snapshot() for _ in range(100)]  # concurrent reads
    for t in threads:
        t.join()
    assert not errors
    assert all(e[1] == "SPAN" for s in snaps for e in s)
    assert len(log) == 256
    assert log.dropped == log.n_total - 256
    assert log.n_total <= n * nthreads   # += under the GIL never overcounts


def test_trace_follows_obs_kill_switch():
    obs.set_enabled(False)
    assert not obs.TRACE.on
    obs.TRACE.emit("FINISH", rid=1)
    assert len(obs.TRACE) == 0
    obs.set_enabled(True)
    assert obs.TRACE.on


def test_trace_env_parse(monkeypatch):
    for off in ("0", "false", " OFF ", "no"):
        monkeypatch.setenv("REPRO_TRACE", off)
        assert not trace._trace_env_on()
    for on in ("", "1", "true", "anything"):
        monkeypatch.setenv("REPRO_TRACE", on)
        assert trace._trace_env_on()
    monkeypatch.delenv("REPRO_TRACE")
    assert trace._trace_env_on()


# --------------------------------------------------------------------------
# Per-request reducer.
# --------------------------------------------------------------------------

_T = 1e-3           # 1 ms between synthetic events


def _preempted_request():
    """rid 7: arrive, wait 2ms, prefill, preempt BEFORE the first token
    (2ms gap -> TTFT wait), resume, first token, preempt AFTER it (3ms
    gap -> decode stall), resume, finish."""
    return [
        (0 * _T, "REQ_ARRIVE", 7, -1, (10, 4), None),
        (2 * _T, "ADMIT", 7, 0, None, None),
        (3 * _T, "PREFILL_CHUNK", 7, 0, (0, 10), 500.0),
        (4 * _T, "PREEMPT", 7, 0, None, None),
        (6 * _T, "RESUME", 7, 1, None, None),
        (7 * _T, "FIRST_TOKEN", 7, 1, None, None),
        (8 * _T, "PREEMPT", 7, 1, None, None),
        (11 * _T, "RESUME", 7, 2, None, None),
        (12 * _T, "FINISH", 7, 2, 4, None),
    ]


def test_reducer_ttft_breakdown_and_decode_stall():
    r = trace.per_request(_preempted_request())[7]
    assert r["queue_wait_us"] == pytest.approx(2000, abs=0.1)
    assert r["ttft_us"] == pytest.approx(7000, abs=0.1)
    # wait = initial 2ms + the pre-first-token preemption gap of 2ms
    assert r["ttft_wait_us"] == pytest.approx(4000, abs=0.1)
    assert r["ttft_prefill_us"] == pytest.approx(3000, abs=0.1)
    assert r["ttft_wait_us"] + r["ttft_prefill_us"] == \
        pytest.approx(r["ttft_us"], abs=0.2)
    # the post-first-token gap (8ms -> 11ms) is decode stall, not TTFT
    assert r["decode_stall_us"] == pytest.approx(3000, abs=0.1)
    assert r["preemptions"] == 2 and r["prefill_chunks"] == 1
    assert r["finished"] and r["n_out"] == 4
    assert r["e2e_us"] == pytest.approx(12000, abs=0.1)


def test_reducer_tolerates_partial_trace():
    """A request whose REQ_ARRIVE fell off the ring anchors at its first
    surviving event instead of raising."""
    evs = [(1.0, "ADMIT", 3, 0, None, None),
           (2.0, "FIRST_TOKEN", 3, 0, None, None),
           (3.0, "FINISH", 3, 0, 2, None)]
    r = trace.per_request(evs)[3]
    assert r["queue_wait_us"] == 0.0
    assert r["finished"] and r["n_out"] == 2


def test_reducer_skips_batch_and_router_events():
    evs = [(0.0, "SPAN", -1, -1, ("serve.decode_issue", "serve.step",
                                   (2, 40)), 12.5),
           (0.1, "ROUTE_MISS", -1, -1, ("gemm", "S", "NN", [4, 8, 8],
                                        "analytical"), None)]
    assert trace.per_request(evs) == {}


def test_observe_folds_reducer_into_registry():
    per = trace.per_request(_preempted_request())
    trace.observe(per)
    h = obs.REGISTRY.get("serve.trace.queue_wait_us")
    assert h is not None and h.count == 1
    assert obs.REGISTRY.get("serve.trace.preemptions").vmax == 2
    s = trace.summary(per)
    assert s["requests"] == 1 and s["finished"] == 1
    assert s["preemptions"] == 2
    assert s["ttft_wait_p50_us"] == pytest.approx(4000, abs=0.1)


# --------------------------------------------------------------------------
# Perfetto export.
# --------------------------------------------------------------------------

def _two_request_stream():
    return _preempted_request() + [
        (0.5 * _T, "REQ_ARRIVE", 8, -1, (4, 2), None),
        (4.5 * _T, "ADMIT", 8, 0, None, None),
        (5.0 * _T, "FIRST_TOKEN", 8, 0, None, None),
        (5.5 * _T, "FINISH", 8, 0, 2, None),
        (2.5 * _T, "ROUTE_MISS", -1, -1, ("gemm", "S", "NN", [4, 8, 8],
                                          "analytical"), None),
        (9 * _T, "EVICT", 7, -1, 2, None),
        (0.1 * _T, "PROFILE_SWAP", -1, -1, "cpu/interpret:3", None),
    ]


def test_perfetto_tracks_slices_and_flows():
    doc = trace.perfetto(_two_request_stream(), slots=3)
    te = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    tracks = {e["args"]["name"] for e in te
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"queue", "slot 0", "slot 1", "slot 2"} <= tracks
    procs = {e["args"]["name"] for e in te
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"repro.serve", "repro.router"}

    slices = [e for e in te if e["ph"] == "X"]
    assert all(e["ts"] >= 0 and e["dur"] > 0 for e in slices)
    r7 = {e["name"] for e in slices if e.get("args", {}).get("rid") == 7}
    assert {"req 7 queued", "req 7 prefill", "req 7 decode",
            "req 7 queued (preempted)"} <= r7

    # the preemption gap is a visible slice on the queue track
    gap = [e for e in slices if e["name"] == "req 7 queued (preempted)"]
    assert len(gap) == 2                 # one per preemption
    assert all(e["tid"] == 0 for e in gap)
    assert sorted(round(e["dur"]) for e in gap) == [2000, 3000]

    # flow chains: per request one start, then continuations, then the
    # terminating step at FINISH
    for rid in (7, 8):
        fl = [e["ph"] for e in te if e["ph"] in ("s", "t", "f")
              and e.get("id") == rid]
        assert fl[0] == "s" and fl[-1] == "f" and "s" not in fl[1:]

    inst = {e["name"] for e in te if e["ph"] == "i"}
    assert {"preempt req 7", "evict req 7", "route_miss",
            "profile_swap"} <= inst


def test_perfetto_closes_unfinished_slices():
    evs = [(0.0, "REQ_ARRIVE", 1, -1, (4, 8), None),
           (0.001, "ADMIT", 1, 0, None, None)]  # never finishes
    doc = trace.perfetto(evs)
    open_names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert "req 1 queued" in open_names  # closed at the capture edge


def test_perfetto_empty_stream():
    assert trace.perfetto([]) == {"traceEvents": [],
                                  "displayTimeUnit": "ms"}


# --------------------------------------------------------------------------
# File + CLI round-trip.
# --------------------------------------------------------------------------

def test_write_trace_roundtrip(tmp_path):
    evs = _two_request_stream()
    p = trace.write_trace(tmp_path / "t.json", evs, slots=3)
    doc = json.loads(p.read_text())
    assert doc["reproTrace"]["schema"] == trace.TRACE_SCHEMA_VERSION
    assert len(doc["reproTrace"]["events"]) == len(evs)
    assert {r["rid"] for r in doc["otherData"]["per_request"]} == {7, 8}
    back = trace.load_events(p)
    # rebased + ns-rounded timestamps preserve every derived metric
    assert trace.per_request(back) == trace.per_request(evs)


def test_load_events_rejects_foreign_and_versioned_files(tmp_path):
    p = tmp_path / "x.json"
    p.write_text(json.dumps({"traceEvents": []}))
    with pytest.raises(ValueError, match="no reproTrace"):
        trace.load_events(p)
    p.write_text(json.dumps({"reproTrace": {"schema": 99, "events": []}}))
    with pytest.raises(ValueError, match="schema"):
        trace.load_events(p)


def test_cli_trace_reexport(tmp_path, capsys):
    from repro.obs.__main__ import main
    src = trace.write_trace(tmp_path / "in.json", _two_request_stream())
    assert main(["trace", str(src), str(tmp_path / "out.json")]) == 0
    out = capsys.readouterr().out
    assert "rid" in out and "wrote" in out
    assert trace.load_events(tmp_path / "out.json")


def test_cli_trace_live_ring(tmp_path, capsys):
    from repro.obs.__main__ import main
    obs.TRACE.emit("REQ_ARRIVE", rid=5, arg=(4, 2))
    obs.TRACE.emit("ADMIT", rid=5, slot=0)
    obs.TRACE.emit("FINISH", rid=5, slot=0, arg=2)
    assert main(["trace", str(tmp_path / "live.json")]) == 0
    assert "wrote" in capsys.readouterr().out
    assert len(trace.load_events(tmp_path / "live.json")) == 3


def test_cli_trace_wrong_arity():
    from repro.obs.__main__ import main
    with pytest.raises(SystemExit):
        main(["trace"])


# --------------------------------------------------------------------------
# Live engine integration: the trace of a real preemption-forcing run.
# --------------------------------------------------------------------------

def test_paged_engine_emits_full_lifecycle():
    cfg = configs.get_smoke("olmo-1b")
    model = registry.build(cfg)
    params = model.init(KEY)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab, 7).astype(np.int32)
               for _ in range(2)]
    # 3 usable blocks x 8 < peak demand -> the younger request preempts
    e = PagedEngine(model, params, XLA, slots=2, max_len=24, eos=-1,
                    block_size=8, chunk=8, num_blocks=4)
    for rid, p in enumerate(prompts):
        e.submit(Request(rid, p, max_new=10))
    done = e.run()
    assert len(done) == 2

    evs = obs.TRACE.snapshot()
    kinds = {e[1] for e in evs}
    assert {"REQ_ARRIVE", "ADMIT", "PREFILL_CHUNK", "FIRST_TOKEN",
            "PREEMPT", "RESUME", "FINISH", "EVICT"} <= kinds
    per = trace.per_request(evs)
    assert set(per) == {0, 1}
    assert all(r["finished"] and r["n_out"] == 10 for r in per.values())
    assert sum(r["preemptions"] for r in per.values()) > 0
    # every chunk event carries a measured duration
    assert all(e[5] > 0 for e in evs if e[1] == "PREFILL_CHUNK")
    # reducer totals agree with the engine's own preemption counter
    assert sum(r["preemptions"] for r in per.values()) == \
        obs.counter("serve.preemptions").value

    doc = trace.perfetto(evs, slots=2)
    slices = [x for x in doc["traceEvents"] if x["ph"] == "X"]
    preempted = [r for r, rec in per.items() if rec["preemptions"]]
    for rid in preempted:
        assert any(x["name"] == f"req {rid} queued (preempted)"
                   for x in slices)


def _span_tree(evs):
    """SPAN records as dicts with their start and end (seconds)."""
    return [{"name": a[0], "parent": a[1], "info": a[2] if len(a) > 2
             else None, "t0": t - d * 1e-6, "t1": t}
            for t, k, _r, _s, a, d in evs if k == "SPAN"]


def _inside(child, spans, name):
    return any(p["name"] == name and p["t0"] <= child["t0"]
               and child["t1"] <= p["t1"] for p in spans)


def test_paged_engine_span_tree():
    """Every working iteration records its span tree, each child inside
    its parent; an idle step() records nothing, and nothing at all is
    recorded with obs off."""
    cfg = configs.get_smoke("olmo-1b")
    model = registry.build(cfg)
    params = model.init(KEY)
    rng = np.random.RandomState(3)

    def engine():
        e = PagedEngine(model, params, XLA, slots=2, max_len=40, eos=-1,
                        block_size=8, chunk=8, drain_every=2)
        for rid, n in enumerate((5, 19, 11)):
            e.submit(Request(rid, rng.randint(0, cfg.vocab, n)
                             .astype(np.int32), max_new=6))
        return e

    e = engine()
    working = idle = 0
    while e.scheduler.has_work() or e._pending:
        n0 = len(obs.TRACE)
        worked = e.step()
        names = [x["name"] for x in _span_tree(obs.TRACE.snapshot()[n0:])]
        if names:
            working += 1
            assert names[-1] == "serve.step"     # the root closes last
        else:
            idle += 1
            assert not worked and len(obs.TRACE) == n0
    assert len(e.done) == 3

    spans = _span_tree(obs.TRACE.snapshot())
    steps = [x for x in spans if x["name"] == "serve.step"]
    assert len(steps) == working
    by = lambda n: [x for x in spans if x["name"] == n]  # noqa: E731
    for name, parent in (("serve.admit", "serve.step"),
                         ("serve.decode_issue", "serve.step"),
                         ("serve.prefill_chunk", "serve.step"),
                         ("serve.drain", "serve.step"),
                         ("serve.drain_wait", "serve.drain"),
                         ("serve.prefill_sample", "serve.prefill_chunk")):
        assert by(name), name
        for x in by(name):
            assert x["parent"] == parent and _inside(x, spans, parent)
    # one boundary sample per final chunk, i.e. per first token here
    firsts = [ev for ev in obs.TRACE.snapshot() if ev[1] == "FIRST_TOKEN"]
    assert len(by("serve.prefill_sample")) == len(firsts) == 3
    assert all(0 <= x["info"] <= (x["t1"] - x["t0"]) * 1e6
               for x in by("serve.prefill_sample"))
    # decode issues carry (live slots, context sum)
    assert all(1 <= x["info"][0] <= 2 and x["info"][1] > x["info"][0]
               for x in by("serve.decode_issue"))
    n = len(obs.TRACE)
    assert e.step() is False and len(obs.TRACE) == n

    obs.set_enabled(False)
    off = engine()
    assert len(off.run()) == 3
    assert len(obs.TRACE) == n                   # nothing while off


def test_paged_engine_preempt_span():
    cfg = configs.get_smoke("olmo-1b")
    model = registry.build(cfg)
    params = model.init(KEY)
    rng = np.random.RandomState(2)
    e = PagedEngine(model, params, XLA, slots=2, max_len=24, eos=-1,
                    block_size=8, chunk=8, num_blocks=4)
    for rid in range(2):
        e.submit(Request(rid, rng.randint(0, cfg.vocab, 7).astype(np.int32),
                         max_new=10))
    e.run()
    evs = obs.TRACE.snapshot()
    spans = _span_tree(evs)
    pre = [x for x in spans if x["name"] == "serve.preempt"]
    victims = [ev[2] for ev in evs if ev[1] == "PREEMPT"]
    assert victims and [x["info"] for x in pre] == victims
    assert all(x["parent"] is not None for x in pre)


def test_perfetto_engine_track_roundtrip(tmp_path):
    """SPAN events render on one "engine" track of the serve process,
    nested by time, and survive the write_trace/load_events round trip."""
    evs = _two_request_stream() + [
        (4.0 * _T, "SPAN", -1, -1, ("serve.decode_issue", "serve.step",
                                    (2, 31)), 300.0),
        (5.0 * _T, "SPAN", -1, -1, ("serve.drain_wait", "serve.drain"),
                                    100.0),
        (5.2 * _T, "SPAN", -1, -1, ("serve.drain", "serve.step"), 400.0),
        (5.5 * _T, "SPAN", -1, -1, ("serve.step", None), 2000.0),
    ]
    back = trace.load_events(trace.write_trace(tmp_path / "t.json", evs,
                                               slots=3))
    assert trace.per_request(back) == trace.per_request(evs)
    te = trace.perfetto(back, slots=3)["traceEvents"]
    tracks = {(e["pid"], e["tid"]): e["args"]["name"] for e in te
              if e["ph"] == "M" and e["name"] == "thread_name"}
    engine = [k for k, v in tracks.items() if v == "engine"]
    assert len(engine) == 1 and engine[0][0] == 1    # the repro.serve pid
    sl = {e["name"]: e for e in te if e["ph"] == "X"
          and (e["pid"], e["tid"]) == engine[0]}
    assert set(sl) == {"serve.step", "serve.decode_issue", "serve.drain",
                       "serve.drain_wait"}
    assert all(e["ts"] >= 0 for e in sl.values())
    for child, parent in (("serve.decode_issue", "serve.step"),
                          ("serve.drain", "serve.step"),
                          ("serve.drain_wait", "serve.drain")):
        c, p = sl[child], sl[parent]
        assert c["args"]["parent"] == parent
        assert p["ts"] <= c["ts"] + 1e-3
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-3
    assert sl["serve.step"]["dur"] == pytest.approx(2000.0)
    assert sl["serve.decode_issue"]["args"]["info"] == [2, 31]


# --------------------------------------------------------------------------
# Online-tuner events: TUNE_CYCLE track + swap/miss attribution.
# --------------------------------------------------------------------------

def test_perfetto_tune_cycle_renders_on_own_tuner_track():
    evs = [(0.0, "ROUTE_MISS", -1, -1,
            ("gemm", "S", "NN", [45, 45, 45], "analytical"), None),
           (0.010, "TUNE_CYCLE", -1, -1, (1, 2, 4, True), 2500.0),
           (0.020, "TUNE_CYCLE", -1, -1, (2, 0, 0, False), None)]
    doc = trace.perfetto(evs)
    te = doc["traceEvents"]
    tracks = {(e["pid"], e["tid"]): e["args"]["name"] for e in te
              if e["ph"] == "M" and e["name"] == "thread_name"}
    router_pid = next(e["pid"] for e in te if e["ph"] == "M"
                      and e["name"] == "process_name"
                      and e["args"]["name"] == "repro.router")
    assert tracks[(router_pid, 0)] == "route/profile"
    assert tracks[(router_pid, 1)] == "online tuner"

    # a cycle with a measured duration is a complete slice spanning
    # backwards from its end-of-cycle emit time, on the tuner's track
    cyc = [e for e in te if e["ph"] == "X" and e["name"] == "tune_cycle"]
    assert len(cyc) == 1
    assert cyc[0]["pid"] == router_pid and cyc[0]["tid"] == 1
    assert cyc[0]["dur"] == pytest.approx(2500.0)
    assert cyc[0]["ts"] == pytest.approx(10000.0 - 2500.0)
    assert cyc[0]["args"]["cycle"] == (1, 2, 4, True)
    # without a duration it degrades to an instant, same track
    inst = [e for e in te if e["ph"] == "i" and e["name"] == "tune_cycle"]
    assert len(inst) == 1 and inst[0]["tid"] == 1
    # the route instants stay off the tuner track
    miss = [e for e in te if e["ph"] == "i" and e["name"] == "route_miss"]
    assert miss and all(e["tid"] == 0 for e in miss)


def test_swap_to_miss_burst_attribution_survives_roundtrip(tmp_path,
                                                           monkeypatch):
    """The debugging story the trace exists for: a PROFILE_SWAP followed
    by the ROUTE_MISS burst it caused, with ordering and args intact
    after the write_trace/load_events roundtrip — generated by the real
    Router/profile machinery, not synthetic tuples."""
    from repro import api
    from repro.tune import profile as profile_mod
    monkeypatch.setenv(profile_mod.CACHE_ENV, str(tmp_path / "cache"))
    profile_mod.clear_active_profile()
    obs.TRACE.reset()
    r = api.Router(api.Policy(backend="auto"))
    dims = [(16, 16, 16), (32, 32, 32), (48, 48, 48)]
    for d in dims:
        r.route("gemm", d, "S", "NN")    # cold misses
        r.route("gemm", d, "S", "NN")    # memo hits: silent
    profile_mod.set_active_profile(None)  # the swap under test
    for d in dims:
        r.route("gemm", d, "S", "NN")    # recompute burst
    try:
        evs = obs.TRACE.snapshot()
        seq = [(e[1], e[4]) for e in evs
               if e[1] in ("ROUTE_MISS", "PROFILE_SWAP")]
        kinds = [k for k, _ in seq]
        # 3 cold misses, one swap, then exactly 3 re-route misses —
        # the memoized hot path emitted nothing
        assert kinds == ["ROUTE_MISS"] * 3 + ["PROFILE_SWAP"] \
            + ["ROUTE_MISS"] * 3
        swap_at = kinds.index("PROFILE_SWAP")
        burst = seq[swap_at + 1:]
        assert [tuple(a[3]) for _, a in burst] == dims

        back = trace.load_events(trace.write_trace(tmp_path / "t.json",
                                                   evs))
        seq2 = [(e[1], e[4]) for e in back
                if e[1] in ("ROUTE_MISS", "PROFILE_SWAP")]
        # args come back as lists after JSON; compare re-normalized
        norm = lambda s: [(k, json.loads(json.dumps(list(a)))
                           if isinstance(a, (tuple, list)) else a)
                          for k, a in s]  # noqa: E731
        assert norm(seq2) == norm(seq)
        # timestamps stay monotone through the rebase
        ts = [e[0] for e in back]
        assert ts == sorted(ts)
    finally:
        profile_mod.clear_active_profile()
