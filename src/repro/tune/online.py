"""repro.tune.online — background traffic-aware re-tuning.

IAAT's run-time stage is *input-aware*: it adapts to the shapes a
deployment actually sees, not to a static offline bucketing.  This
module is that consumer.  :class:`OnlineTuner` periodically folds
``obs.ROUTES.windowed(decay=...)`` — the exponentially-decayed observed
shape distribution the route memo maintains at zero hot-path cost —
into a traffic-weighted priority over size classes, re-times the top-k
hot ones through :func:`repro.tune.search.budgeted_sweep` (the roofline
prior prunes candidates, so a cycle costs at most ``budget`` stopwatch
timings), and merges the delta into the live :class:`DeviceProfile`
via ``merge`` + ``set_active_profile``.  The swap invalidates the
Router's decision memo and emits ``PROFILE_SWAP``, so tuned-mode
dispatch picks the new entries up on its next trace — the engine never
restarts.

Safety story (proved by the differential suite in
``tests/test_serve_fuzz.py``): routing decisions live at jit *trace*
time, so a profile swap can change which kernel a NEW compilation
picks but never the numerics of an already-compiled serving step; and
every entry the tuner installs is a measured pallas/XLA pair, so a
decision flip only ever trades one correct kernel for another.
Routing decisions may change — results may not.

The whole feature sits behind a kill switch: ``REPRO_ONLINE_TUNE=0``
makes :meth:`OnlineTuner.start` a no-op (manual :meth:`cycle` calls
still work, for tests).

Observability: each cycle bumps ``tune.online.cycles`` /
``tune.online.classes_retuned`` / ``tune.online.swaps``, records its
wall time in ``tune.online.cycle_us``, and lands a ``TUNE_CYCLE`` event
(with the cycle duration) in the flight recorder on the tuner's own
Perfetto track.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro import obs, runtime
from repro.tune import classes as classes_mod
from repro.tune.classes import SizeClass
from repro.tune.profile import DeviceProfile, active_profile, \
    current_device_kind, set_active_profile
from repro.tune.search import TuneTarget

__all__ = ["OnlineTuner", "CycleReport", "weighted_targets", "enabled",
           "KILL_SWITCH_ENV"]

KILL_SWITCH_ENV = "REPRO_ONLINE_TUNE"

#: route-log ops that route per-group problems through the grouped
#: kernels (measured by ``tune_grouped_class``, recorded under the
#: profile's ``grouped:`` namespace); everything else re-times as 2-D.
_GROUPED_OPS = ("batched_gemm", "ragged_gemm")


def enabled() -> bool:
    """The ``REPRO_ONLINE_TUNE`` kill switch (default on; only explicit
    off values disable — same parse as ``REPRO_OBS``)."""
    v = os.environ.get(KILL_SWITCH_ENV)
    return (v or "1").strip().lower() not in ("0", "false", "off", "no")


def weighted_targets(folded: Dict[Tuple[str, str, str], float], *,
                     min_weight: float = 1.0,
                     done: Optional[Dict[Tuple[str, str], float]] = None,
                     retune_ratio: float = 1.5,
                     top_k: Optional[int] = None,
                     max_dim: Optional[int] = None) -> List[TuneTarget]:
    """Fold a ``ROUTES.windowed(decay=...)`` dict into a re-tune
    priority list, hottest first.

    ``folded`` maps ``(op, letter, cls)`` to a decayed count.  Ops
    collapse to the measuring ``kind`` ("gemm" for 2-D/ND, "grouped"
    for the batched/ragged paths — their class strings already describe
    the per-group (C, N, K) problem), weights summing across ops of the
    same kind.  Classes below ``min_weight`` are cold traffic — noise,
    not worth a stopwatch.  ``done`` maps ``(kind, class-key)`` to the
    weight at which a class was last tuned: it is skipped until its
    current weight exceeds ``retune_ratio`` times that, so steady
    traffic is tuned once and only a real shift re-tunes (without this
    every cycle would re-burn the budget on the same top-k).
    ``max_dim`` drops classes whose representative exceeds it — the
    cost valve that keeps a huge one-off shape from eating a cycle.
    """
    acc: Dict[Tuple[str, str], Tuple[float, SizeClass]] = {}
    for (op, letter, cls), w in folded.items():
        kind = "grouped" if op in _GROUPED_OPS else "gemm"
        try:
            sc = SizeClass.from_key(f"{letter}/NN/{cls}")
        except (ValueError, TypeError):
            continue
        if max_dim is not None and \
                max(classes_mod.representative(sc)) > max_dim:
            continue
        key = (kind, sc.key)
        prev = acc.get(key)
        acc[key] = (w + (prev[0] if prev else 0.0), sc)
    out: List[TuneTarget] = []
    for (kind, sckey), (w, sc) in acc.items():
        if w < min_weight:
            continue
        if done is not None and w <= retune_ratio * done.get((kind, sckey),
                                                             0.0):
            continue
        out.append(TuneTarget(kind, sc, w))
    out.sort(key=lambda t: (-t.weight, t.kind, t.sc.key))
    return out[:top_k] if top_k is not None else out


@dataclasses.dataclass(frozen=True)
class CycleReport:
    """What one :meth:`OnlineTuner.cycle` did (returned for tests/CLI;
    the same numbers land in the ``tune.online.*`` metrics)."""
    cycle: int
    considered: int            # hot classes that passed the weighter
    retuned: int               # classes actually re-timed this cycle
    timings: int               # stopwatch budget spent
    swapped: bool              # a merged profile went live
    wall_us: float


class OnlineTuner:
    """Background re-tuner: windowed traffic in, live profile swaps out.

    Drive it either way:

    * ``start()`` / ``stop()`` — a daemon thread runs :meth:`cycle`
      every ``interval_s`` seconds; ``stop`` is idempotent, safe to
      call with requests in flight (the engine's compiled steps never
      consult the tuner) and joins the thread with a timeout.
      :class:`repro.serve.PagedEngine` accepts ``tuner=`` and handles
      this lifecycle around ``run()``.
    * ``cycle()`` — one synchronous pass, for tests and CLI use.

    ``sweeper`` injects the measuring stage (same contract as
    ``search.budgeted_sweep``: ``f(targets, budget=) -> (delta_profile,
    tuned, timings)``) so unit tests exercise the weighting/merge/swap
    plumbing without jax timing.
    """

    def __init__(self, *, interval_s: float = 5.0, top_k: int = 4,
                 budget: int = 8, decay: float = 0.5, n_buckets: int = 8,
                 min_weight: float = 1.0, retune_ratio: float = 1.5,
                 top: int = 1, warmup: int = 0, reps: int = 1,
                 interpret: Optional[bool] = None, grouped_G: int = 4,
                 max_dim: Optional[int] = 1024,
                 device_kind: Optional[str] = None,
                 sweeper: Optional[Callable[..., tuple]] = None,
                 persist: bool = False):
        self.interval_s = interval_s
        self.top_k, self.budget = top_k, budget
        self.decay, self.n_buckets = decay, n_buckets
        self.min_weight, self.retune_ratio = min_weight, retune_ratio
        self.top, self.warmup, self.reps = top, warmup, reps
        self.interpret = runtime.pallas_interpret(interpret)
        self.grouped_G = grouped_G
        self.max_dim = max_dim
        self.mode = "interpret" if self.interpret else "compiled"
        self._device_kind = device_kind
        self._sweeper = sweeper
        self.persist = persist
        self.cycles = 0
        self.swaps = 0
        # (kind, class-key) -> traffic weight when last tuned; consulted
        # by the weighter so steady traffic is tuned once per shift
        self._done: Dict[Tuple[str, str], float] = {}
        self._cycle_lock = threading.Lock()     # one cycle at a time
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- one pass ----------------------------------------------------------

    def targets(self) -> List[TuneTarget]:
        """The weighter: current windowed traffic -> re-tune priorities."""
        folded = obs.ROUTES.windowed(self.n_buckets, decay=self.decay)
        return weighted_targets(folded, min_weight=self.min_weight,
                                done=self._done,
                                retune_ratio=self.retune_ratio,
                                top_k=self.top_k, max_dim=self.max_dim)

    def _sweep(self, targets: Sequence[TuneTarget]):
        if self._sweeper is not None:
            return self._sweeper(targets, budget=self.budget)
        from repro.tune import search
        return search.budgeted_sweep(
            targets, budget=self.budget, top=self.top, warmup=self.warmup,
            reps=self.reps, interpret=self.interpret,
            grouped_G=self.grouped_G, device_kind=self._device_kind)

    def _merge_and_swap(self, delta: DeviceProfile) -> bool:
        """Fold the cycle's delta into the live profile and publish it.
        ``merge`` keeps whichever entry measured faster (``better_than``),
        so an online entry only displaces an offline one it beat; the
        publish is ONE ``set_active_profile`` call, which atomically
        replaces the profile object, staleness-bumps the route memo and
        emits ``PROFILE_SWAP``.  Mode/device-kind mismatches (e.g. an
        interpret-mode cycle while a compiled profile is live) skip the
        merge rather than poison comparable timings."""
        base = active_profile()
        if base is not None and len(base):
            if base.device_kind != delta.device_kind \
                    or base.mode != delta.mode:
                obs.counter("tune.online.merge_skips").inc()
                return False
            merged = base.merge(delta)
        else:
            merged = delta
        set_active_profile(merged)
        self.swaps += 1
        obs.counter("tune.online.swaps").inc()
        if self.persist:
            try:
                merged.save()
            except OSError:
                obs.counter("tune.online.persist_failures").inc()
        return True

    def cycle(self) -> CycleReport:
        """One synchronous pass: weigh traffic, re-tune within budget,
        merge + swap.  Serialized — a manual call during a background
        run waits for the in-flight cycle."""
        with self._cycle_lock:
            t0 = time.perf_counter()
            targets = self.targets()
            delta: Optional[DeviceProfile] = None
            tuned: List[TuneTarget] = []
            timings = 0
            if targets:
                delta, tuned, timings = self._sweep(targets)
            swapped = False
            if delta is not None and len(delta):
                swapped = self._merge_and_swap(delta)
            for t in tuned:
                key = (t.kind, t.sc.key)
                self._done[key] = max(t.weight, self._done.get(key, 0.0))
            self.cycles += 1
            wall_us = (time.perf_counter() - t0) * 1e6
            obs.counter("tune.online.cycles").inc()
            if tuned:
                obs.counter("tune.online.classes_retuned").inc(len(tuned))
            obs.histogram("tune.online.cycle_us").record(wall_us)
            obs.TRACE.emit(
                "TUNE_CYCLE",
                arg=(self.cycles, len(tuned), timings, bool(swapped)),
                dur_us=wall_us)
            return CycleReport(self.cycles, len(targets), len(tuned),
                               timings, swapped, wall_us)

    # -- background lifecycle ----------------------------------------------

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> bool:
        """Start the background loop; returns False when the
        ``REPRO_ONLINE_TUNE=0`` kill switch is set (tuner stays inert).
        Idempotent — a second start while running is a no-op True."""
        if not enabled():
            return False
        if self.running:
            return True
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop,
                                        name="repro-online-tuner",
                                        daemon=True)
        self._thread.start()
        return True

    def _loop(self) -> None:
        # wait FIRST: traffic needs a beat to accumulate, and a
        # stop() right after start() exits without a cycle
        while not self._stop.wait(self.interval_s):
            try:
                self.cycle()
            except Exception:   # noqa: BLE001 — tuning must never kill serving
                obs.counter("tune.online.errors").inc()

    def stop(self, timeout: float = 30.0) -> bool:
        """Signal and join the background loop; True when the thread is
        fully down (always, barring a wedged in-flight cycle).  Safe
        mid-serve and idempotent; the tuner can be start()ed again."""
        t, self._thread = self._thread, None
        if t is None:
            return True
        self._stop.set()
        t.join(timeout)
        return not t.is_alive()

    def __enter__(self) -> "OnlineTuner":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
