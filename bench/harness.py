"""One cell of the benchmark: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix, cell or
metric is a file found by its name:

- ``BENCHMARK.json``: the cell's configuration, mix and metrics;
- ``bench/configs/<config>.json``: the sizes as run (``config``), over
  the program's architecture ``arch``;
- ``bench/mixes/<mix>.json``: traffic and the engine's deployment knobs;
- ``bench/workloads/<cell>.json``: the cell's fixed parameters (the rate
  of an open loop) and the limits of its check;
- ``bench/metrics/<metric>.py``: ``read(run) -> float`` per metric;
- ``bench/families/<family>.py``: operation counts of a decode step;
- ``bench/reference/<family>.py``: the plain reference and its weights.

The program is driven through ``PagedEngine.submit`` / ``step``, exactly
as a server would; the harness only watches the requests it submitted.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
import tempfile
import time
import types
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import traffic

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: Seconds a window's requests may take to finish after it closes.
DRAIN_S = 60.0
#: Seconds of the traced slice, at most a third of the window.  The slice
#: is the end of the window: stopping the profiler holds the host while
#: it collects the trace (about ten seconds per traced second for the
#: 48-layer model on a TPU v5e), so that pause falls after the window.
TRACE_S = 2.0
#: When set, a path the raw trace of the slice is copied to (for the
#: recorded fixture the trace-reduction tests read).
KEEP_TRACE: Optional[str] = None
#: Token id the engine treats as end of sequence: sampling only returns
#: real ids (>= 0), so every request yields exactly its drawn length.
NO_EOS = -1
#: Served tokens the check must compare at the least.
MIN_COMPARED = 100


# --------------------------------------------------------------------------
# The cell, from its files.
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                   # bench/configs/<config>.json
    mix: dict                      # bench/mixes/<mix>.json
    fixed: dict                    # bench/workloads/<cell>.json
    end_to_end: List[dict]         # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    root: pathlib.Path

    @property
    def bench(self) -> pathlib.Path:
        return self.root / "bench"

    @property
    def family(self) -> str:
        return self.config["config"]["family"]

    def shape(self, param_dtype: str) -> types.SimpleNamespace:
        """The sizes as the references and operation counts read them."""
        return types.SimpleNamespace(**self.config["config"],
                                     param_dtype=param_dtype)


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(entry: dict, cell: str) -> bool:
    return cell in entry.get("workloads", [cell])


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(wl)}")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name=name, chips=w["chips"],
                config=_json(root / cfg_entry["file"]),
                mix=_json(root / "bench" / "mixes" / f"{w['traffic']}.json"),
                fixed=_json(root / "bench" / "workloads" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, root=root)


def load_module(path: pathlib.Path, name: Optional[str] = None):
    """A module from a file, by path (metric files have dots in their
    names, so they are not importable by name)."""
    if not path.is_file():
        raise SystemExit(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_file_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(cell: Cell):
    """The program's configuration object, with every size the file
    states put in place of the program's own."""
    from repro import configs
    from repro.configs import base
    fields = dict(cell.config["config"])
    nested = {"attn": base.AttentionPattern, "ssm": base.SSMConfig,
              "moe": base.MoEConfig}
    for k, cls in nested.items():
        if isinstance(fields.get(k), dict):
            fields[k] = cls(**fields[k])
    return dataclasses.replace(configs.get_config(cell.config["arch"]),
                               **fields)


def seed_key(seed: int):
    """A PRNG key from any whole number, wider than 32 bits included."""
    import jax
    lo, hi = seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


# --------------------------------------------------------------------------
# What a run records, and what the metric readers see.
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    rid: int
    arrival: float                 # scheduled (open) or submit (closed)
    first: Optional[float] = None
    finish: Optional[float] = None
    n_out: int = 0


@dataclasses.dataclass
class Run:
    cell: Cell
    seconds: float
    seed: int
    shape: Any = None              # configuration namespace
    family: Any = None             # bench/families/<family>.py
    peak: Optional[dict] = None    # bench/peaks.json row of this device
    t0: float = 0.0                # window start (perf_counter)
    setup_s: float = 0.0
    reqs: List[Req] = dataclasses.field(default_factory=list)
    tokens_in_window: int = 0
    lateness: List[float] = dataclasses.field(default_factory=list)
    admit: Dict[int, float] = dataclasses.field(default_factory=dict)
    trace_dropped: int = 0
    occupancy: Optional[tuple] = None      # (sum, n) over the window
    routes: Dict[tuple, tuple] = dataclasses.field(default_factory=dict)
    slots: int = 0
    decode_issues: List[tuple] = dataclasses.field(default_factory=list)
    prefill_issues: List[float] = dataclasses.field(default_factory=list)
    loaded: List[tuple] = dataclasses.field(default_factory=list)
    trace: Any = None              # xtrace.Reduced of the traced slice
    clock: Optional[tuple] = None  # (trace ns, perf_counter s) pair
    compiles_in_window: int = 0
    trace_busy: float = 0.0        # seconds the device was busy in the slice
    trace_window_s: float = 0.0
    breakdown: Optional[dict] = None

    def window_reqs(self) -> List[Req]:
        """Requests whose (scheduled) arrival fell in the window."""
        return [r for r in self.reqs if r.arrival < self.t0 + self.seconds]

    def to_trace_ns(self, t: float) -> float:
        ns, pc = self.clock
        return ns + (t - pc) * 1e9


# --------------------------------------------------------------------------
# The traced slice.
# --------------------------------------------------------------------------

class Tracer:
    """Traces the last ``TRACE_S`` seconds of the window, and on into the
    drain until a decode step and a prefill chunk have been issued inside
    the slice and ``SETTLE_S`` has passed since, so that every per-layer
    reader finds its program there; marks the slice with
    ``bench.window``."""

    #: Seconds the slice runs on after the work it waits for was issued,
    #: so that the device has run it before the profiler stops.
    SETTLE_S = 0.25

    def __init__(self, run: Run):
        self.run = run
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.length = min(TRACE_S, run.seconds / 3)
        self.state = "before"
        self.ann = None

    def tick(self, now: float) -> None:
        """Called by the window loop; ``run.t0`` is set by then."""
        import jax
        close = self.run.t0 + self.run.seconds
        if self.state == "before" and now >= close - self.length:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            t = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.start_s = time.perf_counter() - t
            self.ann = jax.profiler.TraceAnnotation("bench.window")
            self.ann.__enter__()
            self.t_start = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and now >= close:
            seen = self._issued()
            if seen is not None and now >= seen + self.SETTLE_S:
                self.stop()

    def _issued(self) -> Optional[float]:
        """When both a decode step and a prefill chunk had been issued
        inside the slice, or None while one is missing."""
        t = self.t_start
        dec = [d[0] for d in self.run.decode_issues if d[0] >= t]
        pre = [p for p in self.run.prefill_issues if p >= t]
        return max(dec[0], pre[0]) if dec and pre else None

    def stop(self) -> None:
        import jax
        if self.state != "on":
            return
        self.t_stop = time.perf_counter()
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - self.t_stop
        self.state = "done"

    def reduce(self) -> None:
        import shutil
        from bench import xtrace
        if self.state != "done":
            raise RuntimeError("the traced slice never ran: the window is "
                               "too short")
        t_red = time.perf_counter()
        try:
            path = xtrace.xplane_path(self.dir)
            if KEEP_TRACE:
                shutil.copy(path, KEEP_TRACE)
            red = xtrace.reduce(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        self.run.trace = red
        self.run.clock = (red.window[0], self.t_start)
        print(f"trace: profiler start {self.start_s} s, stop {self.stop_s} s,"
              f" reduction {time.perf_counter() - t_red} s, "
              f"{len(red.ops)} device operations", file=sys.stderr)


def _instrument(eng, run: Run) -> None:
    """Host spans around the engine's calls into each layer, and the live
    slots and context of every decode step (traced runs only)."""
    import jax
    ann = jax.profiler.TraceAnnotation

    def wrap(name, fn, before=None):
        def call(*a):
            if before is not None:
                before(*a)
            with ann(name):
                return fn(*a)
        return call

    def note_decode(dec):
        run.decode_issues.append((time.perf_counter(), len(dec),
                                  sum(q.pos + 1 for q in dec)))

    eng._issue_decode = wrap("bench.decode_issue", eng._issue_decode,
                             note_decode)
    eng._prefill_chunk = wrap(
        "bench.prefill_chunk", eng._prefill_chunk,
        lambda *a: run.prefill_issues.append(time.perf_counter()))
    eng._drain = wrap("bench.drain", eng._drain)
    eng.step = wrap("bench.step", eng.step)


# --------------------------------------------------------------------------
# Set-up, window, check.
# --------------------------------------------------------------------------

def _on_compile(run_box):
    def listener(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration" \
                and run_box.get("in_window"):
            run_box["compiles"] += 1
    return listener


def build_engine(cell: Cell, seed: int):
    """Configuration, weights and engine, exactly as a server builds
    them; returns (model, params, engine, shape, reference module)."""
    import jax
    from repro import api
    from repro.models.registry import build
    from repro.serve import PagedEngine
    from bench.reference import common
    cfg = model_config(cell)
    model = build(cfg)
    shape = cell.shape(cfg.param_dtype)
    ref = load_module(cell.bench / "reference" / f"{cell.family}.py",
                      f"bench.reference.{cell.family}")
    key = seed_key(seed)
    template = jax.eval_shape(model.init, key)
    params = jax.jit(lambda k: common.make_params(ref.rules(shape),
                                                  template, k))(key)
    eng_kw = cell.mix["engine"]
    be = api.install(api.named_policy(eng_kw["policy"]))
    eng = PagedEngine(model, params, be, slots=eng_kw["slots"],
                      max_len=eng_kw["max_len"], temperature=0.0, eos=NO_EOS,
                      seed=seed & 0x7FFFFFFF)
    return model, params, eng, shape, ref


def warm_up(eng) -> None:
    """One throwaway request: the prefill chunk (twice, so both a middle
    and a final chunk run), the boundary sample, the decode step and the
    drain.  Those are the only shapes the window uses."""
    from repro.serve import Request
    n = eng.chunk + 8
    eng.submit(Request(-1, np.arange(n, dtype=np.int32) % 97,
                       max_new=eng.drain_every + 2))
    eng.run()
    eng.done.clear()


@dataclasses.dataclass
class Session:
    """A built and warmed engine, ready for windows."""
    model: Any
    params: Any
    eng: Any
    shape: Any
    ref: Any
    family: Any
    routes: Dict[tuple, tuple]
    box: dict


def open_session(cell: Cell, seed: int,
                 hook: Optional[Callable] = None) -> Session:
    """Configuration, weights, engine and warm-up.  ``hook(engine)`` may
    replace parts of the engine after warm-up (the fault tests break the
    timed path this way)."""
    import jax
    from repro import obs, runtime
    runtime.enable_compile_cache()
    box = {"in_window": False, "compiles": 0}
    jax.monitoring.register_event_duration_secs_listener(_on_compile(box))
    t0 = time.perf_counter()
    model, params, eng, shape, ref = build_engine(cell, seed)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    family = load_module(cell.bench / "families" / f"{cell.family}.py")
    obs.ROUTES.reset()
    warm_up(eng)
    print(f"setup: weights and engine {t1 - t0} s, warm-up "
          f"{time.perf_counter() - t1} s", file=sys.stderr)
    routes = {key[:4]: (h[0], h[3]) for key, h in obs.ROUTES.hits.items()}
    if hook is not None:
        hook(eng)
    return Session(model, params, eng, shape, ref, family, routes, box)


def serve(ses: Session, cell: Cell, seed: int, seconds: float, trace: bool,
          t_start: float):
    """The measured window (and, open loop, its drain); returns
    (run, plan)."""
    import jax
    from repro import obs
    from repro.serve import Request
    run = Run(cell=cell, seconds=seconds, seed=seed, shape=ses.shape,
              family=ses.family, routes=ses.routes, slots=ses.eng.slots)
    plan = traffic.make_plan(cell.mix, cell.fixed, seed, seconds,
                             ses.shape.vocab)
    if trace:
        _instrument(ses.eng, run)
    jax.block_until_ready((ses.eng._ps, ses.eng._cur))
    obs.REGISTRY.reset()
    obs.TRACE.reset()
    ses.box["compiles"] = 0
    run.setup_s = time.perf_counter() - t_start
    _window(run, ses.eng, plan, Request, Tracer(run) if trace else None,
            ses.box)
    return run, plan


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, hook: Optional[Callable] = None,
             control: bool = False, log=print) -> dict:
    """One run of one cell; returns the result line as a dict.

    With ``control`` the control stands in the program's place: the
    line's ``checks`` and ``correct`` are those of the reference in the
    lower precision, read at the same served positions and judged by the
    same limits, and the program's own are under ``program_checks``."""
    import gc
    import jax
    ses = open_session(cell, seed, hook)
    run, plan = serve(ses, cell, seed, seconds, trace, t_start)
    eng = ses.eng
    dev_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()[:cell.chips])
    finished = {r.rid: (plan.prompts[r.rid], list(eng.done[r.rid]))
                for r in run.reqs if r.finish is not None}
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": int(dev_peak)}
    _log_window(run, log)
    # free the program's state before the reference runs
    ref, shape = ses.ref, ses.shape
    del eng, ses
    gc.collect()

    checks, ctl_gap = _check(run, cell, shape, ref, seed, finished, plan,
                             log, control)
    prog_checks = checks
    if control:
        checks = dict(checks, max_logit_gap=dict(checks["max_logit_gap"],
                                                 value=ctl_gap))
    correct = judge(checks)
    metrics = _metrics(run, cell, trace)
    if trace:
        device["busy_s"] = run.trace_busy
        device["window_s"] = run.trace_window_s
    open_loop = cell.mix["loop"] == "open"
    attempted = len(run.window_reqs()) if open_loop else len(run.admit)
    failed = sum(1 for r in run.window_reqs() if r.finish is None) \
        if open_loop else 0
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = run.breakdown
    if control:
        out["program_checks"] = prog_checks
        out["program_correct"] = judge(prog_checks)
    out["checks"] = checks
    return out


def judge(checks: dict) -> bool:
    """Correct when every number compared is there and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def _log_window(run: Run, log) -> None:
    late = sorted(run.lateness)
    fin = sum(1 for r in run.reqs if r.finish is not None)
    log(f"window: {len(run.reqs)} requests submitted, {fin} finished, "
        f"{run.tokens_in_window} tokens delivered in {run.seconds} s; "
        f"generator late p90 {percentile(late, 90)} s max "
        f"{late[-1] if late else None} s; compiles in window "
        f"{run.compiles_in_window}; flight-recorder events dropped "
        f"{run.trace_dropped}", file=sys.stderr)


def _window(run: Run, eng, plan, Request, tracer, box) -> None:
    import jax
    from repro import obs
    from repro.serve import sched
    open_loop = plan.arrivals is not None
    n = len(plan.prompts)
    live: Dict[int, tuple] = {}
    slots = eng.slots
    run.t0 = t0 = time.perf_counter()
    close = t0 + run.seconds
    box["in_window"] = True
    loaded_since = None
    nxt = 0

    def submit(i, due, now):
        eng.submit(Request(i, plan.prompts[i], max_new=int(plan.max_new[i])))
        seq = eng.scheduler.queue[-1]
        rec = Req(i, due)
        run.reqs.append(rec)
        live[i] = (rec, seq)
        if open_loop:
            run.lateness.append(now - due)

    while True:
        now = time.perf_counter()
        if open_loop:
            while nxt < n and t0 + plan.arrivals[nxt] <= now:
                submit(nxt, t0 + plan.arrivals[nxt], now)
                nxt += 1
        elif now < close:
            while len(eng.scheduler.queue) < slots:
                if nxt >= n:
                    raise RuntimeError("closed-loop pool exhausted; raise "
                                       "the mix's pool")
                submit(nxt, now, now)
                nxt += 1
        if tracer is not None:
            tracer.tick(now)
        worked = eng.step()
        if not worked and eng._pending:
            eng._drain()
            worked = True
        t = time.perf_counter()
        for rid in list(live):
            rec, seq = live[rid]
            k = len(seq.out)
            if k != rec.n_out:
                if t < close:
                    run.tokens_in_window += k - rec.n_out
                rec.n_out = k
                if rec.first is None:
                    rec.first = t
            if seq.state == sched.DONE:
                rec.finish = t
                del live[rid]
        busy_now = bool(eng.scheduler.live)
        if busy_now and loaded_since is None:
            loaded_since = t
        elif not busy_now and loaded_since is not None:
            run.loaded.append((loaded_since, t))
            loaded_since = None
        if t >= close:
            box["in_window"] = False
            if not open_loop:
                break
            if (nxt >= n and not live) or t >= close + DRAIN_S:
                break
        if not worked and open_loop and nxt < n and not live:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(t0 + plan.arrivals[nxt], close)
                               - time.perf_counter()))
    if loaded_since is not None:
        run.loaded.append((loaded_since, time.perf_counter()))
    jax.block_until_ready((eng._ps, eng._cur))
    if tracer is not None:
        tracer.stop()
    run.compiles_in_window = box["compiles"]
    occ = obs.REGISTRY.get("serve.slot_occupancy")
    run.occupancy = (occ.total, occ.n) if occ is not None else None
    run.trace_dropped = obs.TRACE.dropped
    run.admit = {ev[2]: ev[0] for ev in obs.TRACE.snapshot()
                 if ev[1] == "ADMIT"}
    if tracer is not None:
        tracer.reduce()
        _trace_summary(run)


def _trace_summary(run: Run) -> None:
    from bench import xtrace
    red = run.trace
    lo, hi = red.window
    run.trace_window_s = (hi - lo) * 1e-9
    run.trace_busy = xtrace.total(xtrace.busy(red)) * 1e-9
    run.breakdown = {"device_ops": xtrace.top_ops(red),
                     "idle_gaps": xtrace.idle_gaps(red, [red.window])}


def _check(run: Run, cell: Cell, shape, ref, seed: int, finished, plan,
           log, control: bool = False):
    """The numbers compared, each with its limit, and with ``control``
    the control's ``max_logit_gap`` on the same positions."""
    import jax
    from bench import check
    from bench.reference import common
    limits = cell.fixed["limits"]
    chk = cell.mix["check"]
    unfinished = sum(1 for r in run.window_reqs() if r.finish is None) \
        if cell.mix["loop"] == "open" else 0
    bad_len = sum(1 for rid, (_p, out) in finished.items()
                  if len(out) != int(plan.max_new[rid])
                  or min(out) < 0 or max(out) >= shape.vocab)
    rids = check.pick(finished, seed, chk["tokens"], chk["max_requests"])
    gap, n_tok, ctl = None, 0, None
    if rids:
        template = _template(cell)
        key = seed_key(seed)
        params = jax.jit(lambda k: common.make_params(ref.rules(shape),
                                                      template, k))(key)
        r = check.Reference(ref, shape, params, cell.mix["engine"]["max_len"],
                            chk["max_requests"])
        g, g_ctl = r.gaps([finished[i] for i in rids], control=control)
        gap, n_tok = float(g.max()), len(g)
        if control:
            ctl = float(g_ctl.max())
        del params, r
    log(f"check: {len(rids)} requests, {n_tok} served tokens compared "
        f"with the reference", file=sys.stderr)
    return {"max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
            "unfinished": {"value": unfinished, "limit": 0},
            "wrong_length": {"value": bad_len, "limit": 0},
            "tokens_short": {"value": max(0, MIN_COMPARED - n_tok),
                             "limit": 0}}, ctl


def _template(cell: Cell):
    import jax
    from repro.models.registry import build
    return jax.eval_shape(build(model_config(cell)).init,
                          jax.random.PRNGKey(0))


def _metrics(run: Run, cell: Cell, trace: bool) -> dict:
    """End-to-end metrics with ``--trace 0``, per-layer with ``--trace 1``."""
    if trace:
        run.peak = peak_row(cell.root)
    return read_metrics(run, cell.per_layer if trace else cell.end_to_end)


class MetricMissing(RuntimeError):
    """A metric that ``BENCHMARK.json`` lists for the cell read nothing."""


def read_metrics(run: Run, entries: List[dict]) -> dict:
    """Each metric by its own file.  ``entries`` are the metrics that
    ``BENCHMARK.json`` lists for this cell, so each has something to read
    there: a reader that returns None is a fault of the run or of the
    reduction, and raises rather than leave the metric out."""
    out = {}
    for m in entries:
        mod = load_module(run.cell.bench / "metrics" / f"{m['name']}.py")
        v = mod.read(run)
        if v is None:
            raise MetricMissing(f"metric {m['name']} found nothing to read "
                                f"in {run.cell.name}")
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def peak_row(root: pathlib.Path) -> dict:
    import jax
    kind = jax.devices()[0].device_kind
    table = _json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]
