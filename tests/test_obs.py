"""repro.obs: registry semantics, histogram accuracy, spans, the Router
shape log / decision memo, the kill switch and the CLI."""
import numpy as np
import pytest

from repro import api, obs
from repro.api import Policy
from repro.tune import classes, profile as profile_mod
from repro.tune.profile import DeviceProfile, ProfileEntry


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.set_enabled(True)
    obs.reset()
    yield
    obs.set_enabled(True)
    obs.reset()


# -- registry ---------------------------------------------------------------

def test_registry_returns_same_object_per_name_and_labels():
    c1 = obs.counter("t.events", op="gemm")
    c1.inc()
    c1.inc(2)
    assert obs.counter("t.events", op="gemm") is c1
    assert c1.value == 3
    # labels are part of the identity, order-insensitively
    assert obs.counter("t.events", op="matmul") is not c1
    assert obs.counter("t.x", a=1, b=2) is obs.counter("t.x", b=2, a=1)


def test_registry_kind_mismatch_raises():
    obs.counter("t.kind")
    with pytest.raises(TypeError):
        obs.gauge("t.kind")


def test_gauge_last_write_wins():
    g = obs.gauge("t.g")
    g.set(1.5)
    g.set(-2)
    assert g.value == -2.0


def test_registry_get_and_collect():
    assert obs.REGISTRY.get("t.absent") is None
    obs.counter("t.a").inc()
    obs.counter("u.b").inc()
    assert list(obs.REGISTRY.collect("t.")) == ["t.a"]
    snap = obs.REGISTRY.snapshot()
    assert snap["t.a"] == {"type": "counter", "value": 1}


# -- histogram --------------------------------------------------------------

def test_histogram_percentiles_track_numpy():
    """Log buckets promise <= sqrt(BASE)-1 ~ 4.4% relative error; check
    against exact numpy percentiles on a latency-shaped distribution."""
    rng = np.random.RandomState(42)
    samples = rng.lognormal(mean=5.0, sigma=1.2, size=2000)
    h = obs.histogram("t.lat_us")
    for s in samples:
        h.record(float(s))
    assert h.count == 2000
    np.testing.assert_allclose(h.mean, samples.mean(), rtol=1e-12)
    for q in (50, 95, 99):
        exact = np.percentile(samples, q)
        assert abs(h.percentile(q) - exact) / exact < 0.06, q
    # extremes are exact, and percentiles clamp inside them
    assert h.vmin == samples.min() and h.vmax == samples.max()
    assert h.percentile(100) <= samples.max()


def test_histogram_zero_and_negative_bucket():
    h = obs.histogram("t.z")
    h.record(0.0)
    h.record(-3.0)
    h.record(10.0)
    assert h.count == 3 and h.zeros == 2
    assert h.percentile(50) == 0.0       # rank 2 of 3 is still a zero
    assert abs(h.p99 - 10.0) / 10.0 < 0.045   # bucket midpoint resolution


def test_histogram_empty():
    h = obs.histogram("t.empty")
    assert h.count == 0 and h.mean == 0.0 and h.p50 == 0.0
    assert h.to_json()["min"] == 0.0


def test_histogram_percentile_edges_are_exact():
    """q<=0 / q>=100 return the observed extremes — not the min/max
    *bucket* midpoints a ceil'd rank would land on."""
    h = obs.histogram("t.edge")
    for v in (3.0, 7.0, 250.0):
        h.record(v)
    assert h.percentile(0) == 3.0
    assert h.percentile(-5) == 3.0
    assert h.percentile(100) == 250.0
    assert h.percentile(150) == 250.0
    # a negative sample is the true minimum, not clamped to the 0 bucket
    h.record(-3.0)
    assert h.percentile(0) == -3.0


def test_histogram_percentile_all_zeros():
    h = obs.histogram("t.allz")
    for _ in range(5):
        h.record(0.0)
    assert h.zeros == 5
    for q in (0, 50, 100):
        assert h.percentile(q) == 0.0


# -- spans ------------------------------------------------------------------

def _spans():
    return [e for e in obs.TRACE.snapshot() if e[1] == "SPAN"]


def test_span_nesting_builds_dotted_paths():
    """Each span's ring record names its parent, children close first,
    and each child lies inside its parent on the recorder's clock."""
    with obs.span("outer"):
        with obs.span("inner"):
            pass
        with obs.span("inner", info=(4, 17)):
            pass
    with obs.span("outer"):
        pass
    ev = _spans()
    assert [e[4] for e in ev] == [("inner", "outer"),
                                  ("inner", "outer", (4, 17)),
                                  ("outer", None), ("outer", None)]
    outer = ev[2]
    for child in ev[:2]:
        assert outer[0] - outer[5] * 1e-6 <= child[0] - child[5] * 1e-6
        assert child[0] <= outer[0]
    assert obs.REGISTRY.collect("span.") == {}   # no histogram any more


def test_span_records_elapsed_time():
    import time
    t_before = time.perf_counter()
    with obs.span("t.sleep") as sp:
        time.sleep(0.01)
        sp.info = "slept"
    (t, etype, rid, slot, arg, dur_us), = _spans()
    assert dur_us >= 9e3 and arg == ("t.sleep", None, "slept")
    assert t_before + dur_us * 1e-6 <= t <= time.perf_counter()
    assert rid == slot == -1


def test_span_recorder_off():
    obs.TRACE.set_enabled(False)         # REPRO_TRACE=0 with metrics on
    try:
        with obs.span("t.off"):
            with obs.span("t.off.inner"):
                pass
    finally:
        obs.TRACE.set_enabled(True)
    assert len(obs.TRACE) == 0
    with obs.span("after"):              # the nesting stack stayed clean
        pass
    assert [e[4] for e in _spans()] == [("after", None)]


# -- the Router shape log / decision memo -----------------------------------

def _route_batch(pol):
    r = api.Router(pol)
    for _ in range(3):
        r.route("matmul", (2, 8, 16, 32), "S", "NN")
    r.route("gemm", (45, 77, 33), "S", "NN")
    r.route("gemm", (45, 77, 33), "S", "NN")
    r.route("batched_gemm", (4, 16, 32, 64), "S", "NN")
    return r


def test_route_log_shape_counts():
    """The acceptance query: counts per (op, dtype, size-class)."""
    _route_batch(Policy(backend="auto"))
    counts = obs.ROUTES.shape_counts()
    assert sum(counts.values()) == 6
    b = classes.bucket_index
    assert counts[("matmul", "S", f"{b(16)}-{b(32)}-{b(16)}")] == 3
    assert counts[("gemm", "S", f"{b(45)}-{b(77)}-{b(33)}")] == 2
    assert counts[("batched_gemm", "S", f"{b(16)}-{b(64)}-{b(32)}")] == 1
    # full-label histogram carries the decision downstream tuning needs
    for (_op, _dt, _tr, _cls, _pallas, source, _blocks), n \
            in obs.ROUTES.histogram().items():
        assert source in ("forced", "profile", "analytical") and n >= 1


def test_route_memo_returns_cached_decision():
    pol = Policy(backend="auto")
    r = api.Router(pol)
    d1 = r.route("gemm", (45, 77, 33), "S", "NN")
    d2 = r.route("gemm", (45, 77, 33), "S", "NN")
    assert d2 is d1                      # memo hit, not a recompute
    # a different Policy object (even equal) must not alias the memo
    d3 = api.Router(Policy(backend="auto")).route(
        "gemm", (45, 77, 33), "S", "NN")
    assert d3 is not d1 and d3 == d1


def test_route_memo_invalidated_by_profile_change(tmp_path, monkeypatch):
    monkeypatch.setenv(profile_mod.CACHE_ENV, str(tmp_path / "cache"))
    profile_mod.clear_active_profile()
    pol = Policy(backend="tuned")
    r = api.Router(pol)
    d1 = r.route("gemm", (45, 45, 45), "S", "NN")
    assert d1.source == "analytical"     # no profile yet
    prof = DeviceProfile(profile_mod.current_device_kind())
    from repro.tune.timer import Measurement
    m = lambda us: Measurement(us, us, us, 3)  # noqa: E731
    prof.record(classes.size_class(45, 45, 45, "S", "NN"),
                ProfileEntry(None, m(100.0), m(1.0)))
    profile_mod.set_active_profile(prof)     # bumps ROUTES.gen
    d2 = r.route("gemm", (45, 45, 45), "S", "NN")
    assert d2.source == "profile" and not d2.use_pallas
    profile_mod.clear_active_profile()
    d3 = r.route("gemm", (45, 45, 45), "S", "NN")
    assert d3.source == "analytical"


def test_route_log_compaction_preserves_counts():
    rl = obs.ROUTES
    old_cap = rl.CAP
    rl.CAP = 4
    try:
        r = api.Router(Policy(backend="auto"))
        for m in range(8, 20):           # 12 distinct shapes > CAP
            r.route("gemm", (m, m, m), "S", "NN")
        assert rl.total == 12            # nothing lost across compactions
        assert len(rl.hits) <= 4
    finally:
        rl.CAP = old_cap


def test_route_log_threaded_note_and_readers():
    """Writers inserting distinct shapes (every route is a memo miss ->
    ``note`` under the lock, repeatedly crossing CAP and compacting)
    race concurrent ``histogram``/``shape_counts`` readers: no
     'dict changed size during iteration', and the exact total survives
    because every write path holds the lock."""
    import threading
    rl = obs.ROUTES
    old_cap = rl.CAP
    rl.CAP = 64
    n_threads, n_shapes = 4, 300
    errors, stop = [], threading.Event()

    def write(tid):
        try:
            r = api.Router(Policy(backend="auto"))
            for i in range(n_shapes):
                m = 8 + tid * n_shapes + i          # distinct across threads
                r.route("gemm", (m, m, m), "S", "NN")
        except Exception as e:                       # pragma: no cover
            errors.append(e)

    def read():
        try:
            while not stop.is_set():
                rl.histogram()
                rl.shape_counts()
        except Exception as e:                       # pragma: no cover
            errors.append(e)

    try:
        ts = [threading.Thread(target=write, args=(t,))
              for t in range(n_threads)]
        ts.append(threading.Thread(target=read))
        for t in ts:
            t.start()
        for t in ts[:-1]:
            t.join()
        stop.set()
        ts[-1].join()
    finally:
        rl.CAP = old_cap
    assert not errors
    assert rl.total == n_threads * n_shapes
    assert len(rl.hits) <= 64


# -- windowed shape observation ---------------------------------------------

def test_routes_windowed_rotation_and_decay():
    b = classes.bucket_index
    ka = ("gemm", "S", f"{b(45)}-{b(77)}-{b(33)}")
    kb = ("gemm", "S", f"{b(300)}-{b(300)}-{b(300)}")
    r = api.Router(Policy(backend="auto"))
    r.route("gemm", (45, 77, 33), "S", "NN")
    w = obs.ROUTES.windowed(4, bucket_s=1.0, now=100.0)
    assert w == [{ka: 1}]                # window opens; nothing closed yet
    r.route("gemm", (45, 77, 33), "S", "NN")
    r.route("gemm", (300, 300, 300), "S", "NN")
    w = obs.ROUTES.windowed(4, bucket_s=1.0, now=101.5)
    assert w == [{}, {ka: 2, kb: 1}]     # bucket closed; fresh one empty
    r.route("gemm", (300, 300, 300), "S", "NN")
    w = obs.ROUTES.windowed(4, bucket_s=1.0, now=102.0)
    assert w == [{kb: 1}, {ka: 2, kb: 1}]   # 0.5s < 1s: still filling
    # decay fold: open bucket weighted 1, previous bucket decay**1
    folded = obs.ROUTES.windowed(4, bucket_s=1.0, decay=0.5, now=102.0)
    assert folded == {kb: 1 + 0.5 * 1, ka: 0.5 * 2}
    # a traffic shift dominates the folded view within one bucket
    assert folded[kb] > folded[ka]


def test_routes_windowed_caps_and_validates():
    r = api.Router(Policy(backend="auto"))
    for i in range(4):
        r.route("gemm", (45, 77, 33), "S", "NN")
        obs.ROUTES.windowed(8, bucket_s=1.0, now=100.0 + i)
    w = obs.ROUTES.windowed(2, bucket_s=1.0, now=110.0)
    assert len(w) == 2                   # n_buckets bounds the view
    with pytest.raises(ValueError):
        obs.ROUTES.windowed(0)
    with pytest.raises(ValueError):
        obs.ROUTES.windowed(2, decay=1.5)
    obs.ROUTES.reset()
    assert obs.ROUTES.windowed(4, bucket_s=1.0, now=200.0) == [{}]


# -- kill switch ------------------------------------------------------------

def test_env_parse_only_explicit_off_disables():
    for off in ("0", "false", "OFF", " no "):
        assert not obs._env_enabled(off)
    for on in (None, "", "1", "true", "yes", "anything"):
        assert obs._env_enabled(on)


def test_disabled_is_noop_everywhere():
    obs.set_enabled(False)
    c = obs.counter("t.dead")
    c.inc(5)
    assert c.value == 0                  # shared null object
    obs.gauge("t.dead_g").set(3)
    obs.histogram("t.dead_h").record(1.0)
    with obs.span("t.dead_span"):
        pass
    _route_batch(Policy(backend="auto"))
    assert obs.ROUTES.total == 0
    assert len(obs.TRACE) == 0           # no SPAN event either
    obs.set_enabled(True)
    assert obs.REGISTRY.snapshot() == {} # nothing leaked into the registry


def test_disabled_routing_still_correct():
    obs.set_enabled(False)
    d = api.Router(Policy(backend="auto")).route(
        "gemm", (45, 77, 33), "S", "NN")
    assert d.source in ("forced", "analytical")
    assert isinstance(d.use_pallas, bool)


# -- the CLI ----------------------------------------------------------------

def _cli(capsys, *argv):
    from repro.obs.__main__ import main
    rc = main(list(argv))
    return rc, capsys.readouterr().out


def test_cli_report_prints_live_registry(capsys):
    obs.counter("t.cli").inc(3)
    rc, out = _cli(capsys, "report")
    assert rc == 0 and "repro.obs report" in out and "t.cli" in out


def test_cli_arity_errors_exit_nonzero():
    from repro.obs.__main__ import main
    # trace with no file or with three; show, which the CLI has not
    for argv in (["trace"], ["trace", "a", "b", "c"], ["show"]):
        with pytest.raises(SystemExit) as ei:
            main(argv)
        assert ei.value.code != 0
