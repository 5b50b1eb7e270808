"""repro.obs — zero-dependency, process-local observability.

Every later perf PR reports through this layer, so it is deliberately
small and stdlib-only: a metric registry (:class:`Counter`,
:class:`Gauge`, log-bucket :class:`Histogram` with p50/p95/p99), a
:class:`span` context manager for host sections (written to the flight
recorder :data:`TRACE` as ``SPAN`` events, and opened as a
``jax.profiler.TraceAnnotation`` so spans line up with device traces
when a profiler is active), and :func:`export_bench`, which writes a
schema'd ``BENCH_<name>.json`` at the repo root — the per-PR perf
trajectory ROADMAP asks for.

The hot-path consumer is ``api.Router.route``: every routing decision is
recorded into :data:`ROUTES`, a shape log keyed by the full call
signature ``(op, dtype, trans, dims, policy)``.  Because a decision is
deterministic given that key plus the active DeviceProfile, the log
doubles as a decision memo — a repeat shape is counted with one dict hit
and returns the cached :class:`~repro.api.Decision` without recomputing,
so routing with observability ON is *faster* than with it off, not just
<5% slower.  The aggregated view (counts per (op, dtype, size-class,
chosen backend/blocks)) is exactly the observed shape distribution the
traffic-aware tuning stage needs (Tillet's input-aware predictor trains
on it; see ROADMAP).

``REPRO_OBS=0`` in the environment disables everything: metric helpers
hand out shared null objects, :class:`span` and the flight recorder do
nothing, and the route log is bypassed with a single attribute check.
"""
from __future__ import annotations

import collections as _collections
import json
import math
import os
import pathlib
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY", "ROUTES",
    "TRACE", "counter", "gauge", "histogram", "span", "enabled",
    "set_enabled", "export_bench", "load_bench", "diff_bench",
    "report_str", "reset", "bench_root", "record_trajectory",
    "BENCH_SCHEMA_VERSION",
]

BENCH_SCHEMA_VERSION = 1

# Histogram bucket growth: bucket i covers [BASE**i, BASE**(i+1)) and
# reports its geometric midpoint, so the worst-case relative error of any
# percentile is sqrt(BASE) - 1 ~ 4.4% — tight enough to rank kernels and
# catch latency regressions, in O(log range) memory per metric.
_BASE = 2.0 ** 0.125
_LOG_BASE = math.log(_BASE)


def _env_enabled(value: Optional[str]) -> bool:
    """``REPRO_OBS`` parse: only explicit off values disable."""
    return (value or "1").strip().lower() not in ("0", "false", "off", "no")


_ENABLED = _env_enabled(os.environ.get("REPRO_OBS"))


def enabled() -> bool:
    """Whether observability is collecting (the ``REPRO_OBS`` switch)."""
    return _ENABLED


def set_enabled(on: bool) -> None:
    """Programmatic kill switch (tests, benchmarks).  Flips the registry,
    the route log, the flight recorder, and spans together so on/off
    comparisons are fair.  (The flight recorder can additionally be
    toggled alone via ``TRACE.set_enabled`` — the trace-overhead gates
    compare trace-ON vs trace-OFF with metrics ON both times.)"""
    global _ENABLED
    _ENABLED = bool(on)
    ROUTES.on = _ENABLED
    TRACE.on = _ENABLED


# --------------------------------------------------------------------------
# Metrics.
# --------------------------------------------------------------------------

class Counter:
    """Monotonic event count."""
    kind = "counter"
    __slots__ = ("n",)

    def __init__(self) -> None:
        self.n = 0

    def inc(self, k: int = 1) -> None:
        self.n += k

    @property
    def value(self) -> int:
        return self.n

    def to_json(self) -> dict:
        return {"type": "counter", "value": self.n}


class Gauge:
    """Last-write-wins instantaneous value."""
    kind = "gauge"
    __slots__ = ("v",)

    def __init__(self) -> None:
        self.v = 0.0

    def set(self, v: float) -> None:
        self.v = float(v)

    @property
    def value(self) -> float:
        return self.v

    def to_json(self) -> dict:
        return {"type": "gauge", "value": self.v}


class Histogram:
    """Log-bucketed distribution with exact count/sum/min/max.

    Non-positive samples land in a dedicated zero bucket (latencies and
    rates are positive; a 0 is usually a degenerate measurement worth
    keeping visible rather than dropping).
    """
    kind = "histogram"
    __slots__ = ("buckets", "zeros", "n", "total", "vmin", "vmax")

    def __init__(self) -> None:
        self.buckets: Dict[int, int] = {}
        self.zeros = 0
        self.n = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def record(self, v: float) -> None:
        v = float(v)
        self.n += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        if v <= 0.0:
            self.zeros += 1
            return
        i = int(math.floor(math.log(v) / _LOG_BASE))
        b = self.buckets
        b[i] = b.get(i, 0) + 1

    @property
    def count(self) -> int:
        return self.n

    @property
    def mean(self) -> float:
        return self.total / self.n if self.n else 0.0

    def percentile(self, q: float) -> float:
        """Value at percentile ``q`` in [0, 100], to bucket resolution.
        The extremes are exact: q<=0 returns the observed minimum and
        q>=100 the observed maximum (a ceil'd rank would otherwise pin
        q=0 to rank 1 and report ~the min *bucket*, not the min)."""
        if self.n == 0:
            return 0.0
        if q <= 0.0:
            return self.vmin
        if q >= 100.0:
            return self.vmax
        rank = max(1, math.ceil(q / 100.0 * self.n))
        seen = self.zeros
        if rank <= seen:
            return 0.0
        for i in sorted(self.buckets):
            seen += self.buckets[i]
            if rank <= seen:
                # geometric midpoint of [BASE**i, BASE**(i+1)), clamped
                # to the exact observed extremes so tails never
                # overshoot reality
                rep = _BASE ** (i + 0.5)
                return min(max(rep, self.vmin), self.vmax)
        return self.vmax

    @property
    def p50(self) -> float:
        return self.percentile(50)

    @property
    def p95(self) -> float:
        return self.percentile(95)

    @property
    def p99(self) -> float:
        return self.percentile(99)

    def to_json(self) -> dict:
        return {"type": "histogram", "count": self.n,
                "sum": self.total, "mean": self.mean,
                "min": self.vmin if self.n else 0.0,
                "max": self.vmax if self.n else 0.0,
                "p50": self.p50, "p95": self.p95, "p99": self.p99}


class _Null:
    """Shared no-op metric handed out when observability is disabled."""
    kind = "null"
    __slots__ = ()
    n = 0
    v = 0.0
    value = 0
    count = 0
    total = 0.0
    mean = 0.0
    p50 = p95 = p99 = 0.0

    def inc(self, k: int = 1) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def record(self, v: float) -> None:
        pass

    def percentile(self, q: float) -> float:
        return 0.0

    def to_json(self) -> dict:
        return {"type": "null"}


_NULL = _Null()


def _key(name: str, labels: Dict[str, Any]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class Registry:
    """Process-local metric store: one object per (name, labels)."""

    def __init__(self) -> None:
        self._metrics: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def _get(self, cls, name: str, labels: Dict[str, Any]):
        if not _ENABLED:
            return _NULL
        key = _key(name, labels)
        m = self._metrics.get(key)
        if m is None:
            with self._lock:
                m = self._metrics.setdefault(key, cls())
        if not isinstance(m, cls):
            raise TypeError(f"metric {key!r} is a {m.kind}, not "
                            f"{cls.kind}")
        return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str, **labels) -> Histogram:
        return self._get(Histogram, name, labels)

    def get(self, name: str, **labels):
        """Lookup without creating; None when never recorded."""
        return self._metrics.get(_key(name, labels))

    def collect(self, prefix: str = "") -> Dict[str, Any]:
        return {k: m for k, m in sorted(self._metrics.items())
                if k.startswith(prefix)}

    def snapshot(self) -> Dict[str, dict]:
        return {k: m.to_json() for k, m in sorted(self._metrics.items())}

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()


REGISTRY = Registry()


def counter(name: str, **labels) -> Counter:
    return REGISTRY.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return REGISTRY.gauge(name, **labels)


def histogram(name: str, **labels) -> Histogram:
    return REGISTRY.histogram(name, **labels)


def reset() -> None:
    """Clear every metric, the route log AND the flight recorder (tests,
    benchmark isolation)."""
    REGISTRY.reset()
    ROUTES.reset()
    TRACE.reset()


# --------------------------------------------------------------------------
# Spans.
# --------------------------------------------------------------------------

_span_stack = threading.local()
_trace_annotation = None     # resolved lazily; False when jax is absent


def _get_trace_annotation():
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _trace_annotation = TraceAnnotation
        except Exception:  # pragma: no cover - jax is a core dep here
            _trace_annotation = False
    return _trace_annotation


class span:
    """Host section of the program: ``with span("serve.drain"): ...``

    On exit it records one ``SPAN`` event in the flight recorder
    (:data:`TRACE`): ``t`` is its end on ``perf_counter``, like every
    other event's, ``dur_us`` its length, and ``arg`` is ``(name,
    parent)``, ``parent`` being the name of the span it ran inside on
    this thread (None at the top), so nesting survives into the ring;
    ``info``, where set before the span closes, is appended as a third
    element.  It also opens a ``jax.profiler.TraceAnnotation`` of the
    same name, so a profiler run shows the section beside the device
    planes.  With the recorder off (``REPRO_OBS=0`` or ``REPRO_TRACE=0``)
    it does nothing beyond one flag check.
    """
    __slots__ = ("name", "info", "_t0", "_parent", "_ann", "_open")

    def __init__(self, name: str, info: Any = None) -> None:
        self.name = name
        self.info = info
        self._open = False

    def __enter__(self) -> "span":
        if not TRACE.on:
            return self
        stack = getattr(_span_stack, "names", None)
        if stack is None:
            stack = _span_stack.names = []
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        ta = _get_trace_annotation()
        self._ann = ta(self.name) if ta else None
        if self._ann is not None:
            self._ann.__enter__()
        self._open = True
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if not self._open:
            return
        t1 = time.perf_counter()
        self._open = False
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        _span_stack.names.pop()
        arg = (self.name, self._parent) if self.info is None \
            else (self.name, self._parent, self.info)
        TRACE.emit("SPAN", arg=arg, dur_us=(t1 - self._t0) * 1e6, t=t1)


# --------------------------------------------------------------------------
# The Router shape log (and decision memo).
# --------------------------------------------------------------------------

class RouteLog:
    """Every ``Router.route`` decision, keyed by the full call signature.

    A live entry is ``key -> [count, policy, gen, decision]`` where
    ``key = (op, letter, trans, dims, id(policy))``.  The holder keeps a
    strong reference to the policy, so the ``is`` check on a hit cannot
    alias a recycled ``id()``; ``gen`` is bumped by ``repro.tune.profile``
    whenever the active DeviceProfile changes, invalidating memoized
    decisions that might have consulted it.  Increments are plain dict
    ops — GIL-atomic enough for metrics (a lost count under a data race
    is acceptable; a torn value is not possible).

    When the table exceeds ``CAP`` distinct keys, live entries are folded
    into the aggregate histogram (per (op, dtype, trans, size-class,
    use_pallas, source, blocks)) and the memo restarts empty — counts are
    never lost, only the memoized Decisions.

    Locking: only the memo-HIT increment (``h[0] += 1`` inline in
    ``Router.route``) is lock-free — a count lost to that race is
    acceptable, a torn value impossible.  ``note`` (the miss path),
    compaction, snapshots and reset all take ``_lock``, so a compaction
    can never iterate a dict another thread is inserting into
    ("dict changed size during iteration") or drop a concurrent note.
    """
    CAP = 32768
    #: windowed() bucket width (seconds) and retention; see below.
    WINDOW_S = 1.0
    MAX_WINDOW_BUCKETS = 64

    def __init__(self) -> None:
        self.on = _ENABLED
        self.gen = 0
        self.hits: Dict[tuple, list] = {}
        self._agg: Dict[tuple, int] = {}
        self._lock = threading.Lock()
        # windowed-shape state: closed buckets (t_start, t_end, counts)
        # newest-first, plus the cumulative snapshot at the last close
        self._win = _collections.deque(maxlen=self.MAX_WINDOW_BUCKETS)
        self._win_prev: Dict[tuple, int] = {}
        self._win_t: Optional[float] = None

    # -- hot path (the .get/.note split lives inline in Router.route) ------

    def note(self, key: tuple, pol, decision) -> None:
        """First sighting of ``key``: memoize the decision, count = 1."""
        with self._lock:
            self.hits[key] = [1, pol, self.gen, decision]
            if len(self.hits) > self.CAP:
                self._compact_locked()

    def invalidate(self) -> None:
        """Active-profile changed: stale every memoized decision (counts
        survive; the next route per key recomputes and re-memoizes)."""
        self.gen += 1

    # -- aggregation (cold) ------------------------------------------------

    @staticmethod
    def _agg_key(key: tuple, d) -> tuple:
        op, letter, trans, dims = key[0], key[1], key[2], key[3]
        from repro.tune.classes import bucket_index  # lazy: cold path only
        if op == "matmul":
            m = 1
            for x in dims[:-2]:
                m *= int(x)
            mnk = (m, int(dims[-1]), int(dims[-2]))
        elif op in ("batched_gemm", "ragged_gemm"):
            # per-group problem (C, N, K) — the unit the Router priced
            mnk = (int(dims[1]), int(dims[3]), int(dims[2]))
        else:
            mnk = (int(dims[0]), int(dims[1]), int(dims[2]))
        cls = "-".join(str(bucket_index(max(1, x))) for x in mnk)
        return (op, letter, trans, cls, d.use_pallas, d.source, d.blocks)

    def _compact_locked(self) -> None:
        """Fold live entries into the aggregate; caller holds ``_lock``."""
        for key, h in self.hits.items():
            ak = self._agg_key(key, h[3])
            self._agg[ak] = self._agg.get(ak, 0) + h[0]
        self.hits.clear()

    def _compact(self) -> None:
        with self._lock:
            self._compact_locked()

    def histogram(self) -> Dict[tuple, int]:
        """Full-label counts: (op, dtype, trans, size-class, use_pallas,
        source, blocks) -> number of route() calls."""
        with self._lock:
            out = dict(self._agg)
            live = list(self.hits.items())
        for key, h in live:
            ak = self._agg_key(key, h[3])
            out[ak] = out.get(ak, 0) + h[0]
        return out

    def decisions(self) -> Dict[tuple, Any]:
        """Live memoized decisions per exact shape:
        ``(op, dtype, trans, dims) -> Decision`` (one per policy; entries
        folded into the aggregate by compaction are not listed)."""
        with self._lock:
            live = list(self.hits.items())
        return {key[:4]: h[3] for key, h in live}

    def shape_counts(self) -> Dict[Tuple[str, str, str], int]:
        """The ROADMAP query: counts per (op, dtype, size-class)."""
        out: Dict[Tuple[str, str, str], int] = {}
        for (op, letter, _tr, cls, *_rest), n in self.histogram().items():
            k = (op, letter, cls)
            out[k] = out.get(k, 0) + n
        return out

    # -- windowed shape observation (the online-tuner feed) ----------------

    def windowed(self, n_buckets: int = 8, *,
                 bucket_s: Optional[float] = None,
                 decay: Optional[float] = None,
                 now: Optional[float] = None):
        """Time-bucketed shape counts — the input-distribution feed for
        online traffic-aware tuning (ROADMAP; Tillet's input-aware
        predictor trains on this, not on the all-time aggregate, so a
        traffic shift shows up within a bucket instead of being averaged
        away).

        Buckets are closed at *observation* time: each call diffs the
        cumulative :meth:`shape_counts` against the snapshot taken at
        the last bucket close, so recording stays entirely on the
        existing memo path (zero extra hot-path cost).  A caller polling
        every ``bucket_s`` seconds (the intended use) gets true
        fixed-width buckets; a slower poller gets one bucket spanning
        the gap — honest, never interpolated.

        Returns newest-first: ``[counts_open, counts_1, ...]`` — the
        open (still-filling) bucket, then up to ``n_buckets - 1`` closed
        ones; each ``counts`` maps ``(op, dtype, size-class) -> n``.
        With ``decay`` in (0, 1], the buckets are instead folded into
        ONE dict of exponentially-decayed weights (bucket *i* weighted
        ``decay**i``) — the sweep-weighting form the tuner consumes
        directly.  ``now`` injects a clock for tests.
        """
        if n_buckets < 1:
            raise ValueError("n_buckets must be >= 1")
        width = bucket_s or self.WINDOW_S
        t = time.monotonic() if now is None else now
        cur = self.shape_counts()
        with self._lock:
            if self._win_t is None:
                self._win_t = t
            elif t - self._win_t >= width:
                delta = {k: cur[k] - self._win_prev.get(k, 0)
                         for k in cur
                         if cur[k] > self._win_prev.get(k, 0)}
                self._win.appendleft((self._win_t, t, delta))
                self._win_prev = cur
                self._win_t = t
            open_bucket = {k: cur[k] - self._win_prev.get(k, 0)
                           for k in cur
                           if cur[k] > self._win_prev.get(k, 0)}
            buckets = [open_bucket] + [c for (_a, _b, c) in
                                       list(self._win)[:n_buckets - 1]]
        if decay is None:
            return buckets
        if not 0.0 < decay <= 1.0:
            raise ValueError("decay must be in (0, 1]")
        folded: Dict[Tuple[str, str, str], float] = {}
        for i, counts in enumerate(buckets):
            w = decay ** i
            for k, n in counts.items():
                folded[k] = folded.get(k, 0.0) + w * n
        return folded

    @property
    def total(self) -> int:
        return sum(self.histogram().values())

    def snapshot(self) -> List[dict]:
        rows = []
        for (op, letter, trans, cls, pallas, source,
             blocks), n in sorted(self.histogram().items(),
                                  key=lambda kv: (-kv[1], kv[0])):
            rows.append({"op": op, "dtype": letter, "trans": trans,
                         "size_class": cls, "use_pallas": pallas,
                         "source": source,
                         "blocks": list(blocks) if blocks else None,
                         "count": n})
        return rows

    def reset(self) -> None:
        with self._lock:
            self.hits.clear()
            self._agg.clear()
            self._win.clear()
            self._win_prev = {}
            self._win_t = None
            self.gen += 1


ROUTES = RouteLog()


# --------------------------------------------------------------------------
# The flight recorder (repro.obs.trace) — event ring + Perfetto export.
# --------------------------------------------------------------------------

from repro.obs import trace  # noqa: E402  (needs nothing above at import)

#: The process-global per-request event ring (see :mod:`repro.obs.trace`).
#: Obeys ``REPRO_OBS`` like every other collector; ``REPRO_TRACE=0``
#: additionally disables just the recorder.
TRACE = trace.TRACE
TRACE.on = TRACE.on and _ENABLED


# --------------------------------------------------------------------------
# BENCH_<name>.json export.
# --------------------------------------------------------------------------

def bench_root() -> pathlib.Path:
    """Where BENCH files land: ``REPRO_BENCH_DIR`` or the repo root
    (three levels above this file — src/repro/obs)."""
    env = os.environ.get("REPRO_BENCH_DIR")
    if env:
        return pathlib.Path(env)
    return pathlib.Path(__file__).resolve().parents[3]


def export_bench(name: str, meta: Optional[dict] = None, *,
                 root: Optional[os.PathLike] = None) -> pathlib.Path:
    """Write the current registry + route log as ``BENCH_<name>.json``.

    The file is the repo's perf-trajectory record: schema-versioned,
    sorted keys, one file per benchmark name so successive PRs diff
    cleanly (``python -m repro.obs diff old.json new.json``).  An
    existing file's ``trajectory`` list (the append-only per-PR history
    written by :func:`record_trajectory`) is carried over, so a fresh
    export refreshes the snapshot without erasing the history."""
    doc = {
        "bench": name,
        "schema": BENCH_SCHEMA_VERSION,
        "created_unix": time.time(),
        "meta": dict(meta or {}),
        "metrics": REGISTRY.snapshot(),
        "router": ROUTES.snapshot(),
    }
    path = pathlib.Path(root) if root else bench_root()
    path.mkdir(parents=True, exist_ok=True)
    out = path / f"BENCH_{name}.json"
    if out.exists():
        try:
            prev = json.loads(out.read_text()).get("trajectory")
            if prev:
                doc["trajectory"] = prev
        except (OSError, ValueError):
            pass        # corrupt old file: overwrite, don't crash the bench
    tmp = out.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    tmp.replace(out)
    return out


def record_trajectory(name: str, entry: dict, *,
                      root: Optional[os.PathLike] = None) -> pathlib.Path:
    """Append one per-PR row to ``BENCH_<name>.json``'s ``trajectory``.

    The trajectory is the longitudinal record ROADMAP asks for: each
    ``benchmarks/run.py --record`` run appends a small dict of headline
    numbers (tokens/s, latency percentiles) stamped with the current
    commit when available, so regressions are visible across PRs, not
    just against the latest snapshot.  Creates a skeleton doc when the
    BENCH file does not exist yet."""
    path = pathlib.Path(root) if root else bench_root()
    path.mkdir(parents=True, exist_ok=True)
    out = path / f"BENCH_{name}.json"
    try:
        doc = json.loads(out.read_text())
    except (OSError, ValueError):
        doc = {"bench": name, "schema": BENCH_SCHEMA_VERSION,
               "created_unix": time.time(), "meta": {}, "metrics": {},
               "router": []}
    row = {"recorded_unix": time.time()}
    commit = _git_head()
    if commit:
        row["commit"] = commit
    row.update(entry)
    doc.setdefault("trajectory", []).append(row)
    tmp = out.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    tmp.replace(out)
    return out


_GIT_HEAD_CACHE: Optional[Tuple[Optional[str]]] = None


def _git_head() -> Optional[str]:
    """Short commit hash of the repo containing this file, or None.
    Memoized per process — HEAD cannot move under a running benchmark,
    and ``record_trajectory`` may be called once per suite."""
    global _GIT_HEAD_CACHE
    if _GIT_HEAD_CACHE is None:
        import subprocess
        try:
            head = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=pathlib.Path(__file__).resolve().parent, timeout=5,
                capture_output=True, text=True, check=True).stdout.strip()
        except Exception:
            head = None
        _GIT_HEAD_CACHE = (head,)
    return _GIT_HEAD_CACHE[0]


def load_bench(path: os.PathLike) -> dict:
    doc = json.loads(pathlib.Path(path).read_text())
    schema = int(doc.get("schema", -1))
    if schema != BENCH_SCHEMA_VERSION:
        raise ValueError(f"{path}: BENCH schema {schema} != supported "
                         f"{BENCH_SCHEMA_VERSION}")
    return doc


def _scalar_metrics(doc: dict) -> Dict[str, float]:
    """Flatten a BENCH doc to comparable scalars (counter/gauge values,
    histogram count/mean/p50/p95/p99)."""
    out: Dict[str, float] = {}
    for key, m in doc.get("metrics", {}).items():
        t = m.get("type")
        if t in ("counter", "gauge"):
            out[key] = float(m["value"])
        elif t == "histogram":
            for f in ("count", "mean", "p50", "p95", "p99"):
                out[f"{key}.{f}"] = float(m[f])
    return out


def diff_bench(a: dict, b: dict) -> List[Tuple[str, Optional[float],
                                               Optional[float],
                                               Optional[float]]]:
    """Rows of (metric, old, new, pct_change); None marks one-sided keys."""
    am, bm = _scalar_metrics(a), _scalar_metrics(b)
    rows: List[Tuple[str, Optional[float], Optional[float],
                     Optional[float]]] = []
    for key in sorted(set(am) | set(bm)):
        old, new = am.get(key), bm.get(key)
        pct = None
        if old is not None and new is not None and old != 0:
            pct = (new - old) / abs(old) * 100.0
        rows.append((key, old, new, pct))
    return rows


def report_str() -> str:
    """Human-readable dump of the live registry + route histogram."""
    lines = ["== repro.obs report =="]
    metrics = REGISTRY.collect()
    if not metrics and not ROUTES.total:
        lines.append("(empty — nothing recorded, or REPRO_OBS=0)")
    for key, m in metrics.items():
        if m.kind == "counter":
            lines.append(f"  {key:<44s} {m.value}")
        elif m.kind == "gauge":
            lines.append(f"  {key:<44s} {m.value:.6g}")
        else:
            lines.append(
                f"  {key:<44s} n={m.count} mean={m.mean:.1f} "
                f"p50={m.p50:.1f} p95={m.p95:.1f} p99={m.p99:.1f}")
    rows = ROUTES.snapshot()
    if rows:
        lines.append(f"  -- router shape histogram "
                     f"({ROUTES.total} decisions) --")
        for r in rows[:20]:
            lines.append(
                f"  {r['op']:<13s} {r['dtype']}/{r['trans']} "
                f"class={r['size_class']:<10s} "
                f"{'pallas' if r['use_pallas'] else 'xla':<6s} "
                f"{r['source']:<10s} x{r['count']}")
        if len(rows) > 20:
            lines.append(f"  ... {len(rows) - 20} more rows")
    return "\n".join(lines)
