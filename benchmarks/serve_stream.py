"""Streaming serving benchmark: Poisson arrivals against both serving
engines (the paper's decode-time small-GEMM regime under a realistic
open-loop load).

Requests arrive by a seeded exponential inter-arrival process and are
submitted at their arrival times to either the paged slot-level engine
(:class:`repro.serve.PagedEngine`, the default) or the wave-based
reference (:class:`repro.serve.ContinuousBatcher`); the engines' own
:mod:`repro.obs` instrumentation then prices everything we report —
time-to-first-token, end-to-end latency (p50/p99), admission wait,
decode throughput, slot/wave occupancy.  ``main()`` exports the numbers
as ``BENCH_serve.json`` with a per-engine summary in ``meta`` so one
file records the paged-vs-wave comparison; ``--model`` repeats to
stream several smoke archs (per-model sections land under
``meta.models`` — this is how the recurrent families get their own
paged rows); ``--gate`` fails the run when any streamed model's paged
p99 end-to-end latency regresses >20% against the checked-in baseline,
and ``--record`` appends a trajectory row (the per-PR history
``benchmarks/run.py --record`` maintains).  ``--trace PATH``
additionally dumps the last paged stream's flight-recorder timeline as
a Chrome-trace/Perfetto JSON (slots as tracks, requests as
flow-connected slices) and the per-request reducer's distributions
(queue wait, TTFT wait-vs-prefill split, decode stall) always land in
the export as ``serve.trace.*``; ``--trace-gate`` fails the run when
tracing costs more than 5% paged tokens/s.  ``--online-tune`` streams
the primary model once more with the background traffic-aware re-tuner
running (``--online-profile PATH`` saves the resulting profile — the
CI artifact) and ``--online-gate`` fails the run when the tuner costs
more than 5% paged tokens/s (same best-of-retries shape as the trace
gate).

    PYTHONPATH=src python benchmarks/serve_stream.py --requests 16
    PYTHONPATH=src python benchmarks/serve_stream.py --engine both --gate
    PYTHONPATH=src python benchmarks/serve_stream.py \
        --model glm4-9b --model mamba2-780m --engine both --record
"""
from __future__ import annotations

import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

GATE_PCT = 20.0     # p99 e2e regression tolerance vs checked-in baseline
TRACE_GATE_PCT = 5.0    # tokens/s loss tolerance with the flight recorder on
ONLINE_GATE_PCT = 5.0   # tokens/s loss tolerance with the online tuner on


def _build_engine(engine, model, params, *, slots, seed):
    from repro.serve import ContinuousBatcher, PagedEngine
    if engine == "paged":
        return PagedEngine(model, params, slots=slots, max_len=128,
                           temperature=0.8, seed=seed, block_size=16,
                           chunk=16)
    return ContinuousBatcher(model, params, slots=slots, max_len=128,
                             temperature=0.8, seed=seed)


def stream(n_requests: int = 16, rate_hz: float = 4.0, *, slots: int = 4,
           max_new: int = 8, prompt_lo: int = 4, prompt_hi: int = 16,
           model_name: str = "glm4-9b", policy: str = "xla",
           seed: int = 0, engine: str = "paged", online: bool = False,
           online_profile=None, online_tuner=None):
    """Run the open-loop stream; returns (meta, wall_s, tokens).

    Arrival times are drawn up front (seeded, reproducible); the loop
    submits every request whose arrival time has passed, runs one engine
    step (a wave for the reference engine, one decode iteration for the
    paged engine), and otherwise sleeps until the next arrival — so
    admission wait honestly includes whatever the scheduler was busy
    with.  The same seed drives both engines, so a ``--engine both``
    comparison sees an identical arrival process and workload.

    ``online=True`` (paged only) runs a background
    :class:`repro.tune.online.OnlineTuner` for the stream's duration —
    the `--online-tune` smoke and the `--online-gate` overhead
    comparison.  ``online_tuner`` injects a caller-owned tuner (the
    gate reuses one across attempts so its done-tracking converges to
    the sweep-free steady state); otherwise a fresh small-budget one is
    built.  ``online_profile`` saves whatever profile the tuner left
    active to that path (the CI artifact); the active profile is
    cleared afterwards either way so later streams start clean.
    """
    import jax
    import numpy as np

    from repro import api, configs, obs
    from repro.serve import Request

    cfg = configs.get_smoke(model_name)
    from repro.models.registry import build
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    api.install(api.named_policy(policy))
    srv = _build_engine(engine, model, params, slots=slots, seed=seed)

    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / rate_hz, size=n_requests)
    prompts = [rng.randint(0, cfg.vocab,
                           rng.randint(prompt_lo, prompt_hi)).astype(np.int32)
               for _ in range(n_requests)]
    arrivals = np.cumsum(gaps)

    # warm the jit caches off the clock: one throwaway request end-to-end
    # (dropped from ``done`` so the stream serves all n_requests and the
    # token/latency counts don't include it).
    srv.submit(Request(-1, prompts[0], max_new=2))
    srv.run()
    srv.done.clear()
    online = online and engine == "paged"
    if online:
        # routing happens at jit TRACE time, so the warmup's route()
        # calls ARE the observed traffic the tuner's windowed feed sees
        # (the compiled steps never re-route); keep ROUTES, reset the
        # rest so latency numbers still exclude the warmup
        obs.REGISTRY.reset()
        obs.TRACE.reset()
    else:
        obs.reset()

    tuner = None
    if online:
        tuner = online_tuner
        if tuner is None:
            from repro.tune.online import OnlineTuner
            tuner = OnlineTuner(interval_s=0.3, budget=4, top=1, reps=1,
                                max_dim=512)
        tuner.start()
    t0 = time.perf_counter()
    try:
        nxt = 0
        while len(srv.done) < n_requests:
            now = time.perf_counter() - t0
            while nxt < n_requests and arrivals[nxt] <= now:
                srv.submit(Request(nxt, prompts[nxt], max_new=max_new))
                nxt += 1
            if not srv.step() and nxt < n_requests:
                time.sleep(max(0.0,
                               arrivals[nxt] - (time.perf_counter() - t0)))
    finally:
        if tuner is not None:
            tuner.stop()
    wall = time.perf_counter() - t0
    tokens = sum(len(v) for v in srv.done.values())
    meta = {
        "engine": engine, "model": model_name, "policy": policy,
        "slots": slots, "requests": n_requests, "rate_hz": rate_hz,
        "max_new": max_new, "seed": seed, "wall_s": round(wall, 3),
        "tokens": tokens, "tokens_per_s": round(tokens / wall, 2),
    }
    if tuner is not None:
        from repro.tune import profile as profile_mod
        meta["online"] = {"cycles": tuner.cycles, "swaps": tuner.swaps}
        if online_profile is not None:
            prof = profile_mod.active_profile()
            if prof is None:        # no swap landed: still emit a valid doc
                prof = profile_mod.DeviceProfile(
                    profile_mod.current_device_kind())
            meta["online"]["profile"] = str(prof.save(online_profile))
            meta["online"]["entries"] = len(prof)
        profile_mod.clear_active_profile()
    return meta, wall, tokens


def _summary(meta):
    """Fold the live registry into one comparable per-engine dict."""
    from repro import obs
    out = {"tokens_per_s": meta["tokens_per_s"], "tokens": meta["tokens"],
           "wall_s": meta["wall_s"]}
    for short, metric in (("ttft", "serve.ttft_us"),
                          ("e2e", "serve.e2e_us"),
                          ("admission_wait", "serve.admission_wait_us")):
        h = obs.REGISTRY.get(metric)
        if h is not None and h.n:
            out[f"{short}_p50_us"] = round(h.p50, 1)
            out[f"{short}_p99_us"] = round(h.p99, 1)
    pre = obs.REGISTRY.get("serve.preemptions")
    if pre is not None:
        out["preemptions"] = pre.value
    return out


def _headline(meta, prefix="serve_stream"):
    from repro import obs
    e2e = obs.REGISTRY.get("serve.e2e_us")
    ttft = obs.REGISTRY.get("serve.ttft_us")
    rows = [(f"{prefix}/tokens_per_s", meta["tokens_per_s"],
             meta["tokens"])]
    if e2e is not None and e2e.n:
        rows += [(f"{prefix}/e2e_p50_us", round(e2e.p50, 1), e2e.n),
                 (f"{prefix}/e2e_p99_us", round(e2e.p99, 1), e2e.n)]
    if ttft is not None and ttft.n:
        rows += [(f"{prefix}/ttft_p50_us", round(ttft.p50, 1), ttft.n)]
    return rows


def bench(engines, **kw):
    """Run the stream per engine (fresh metrics each) and return
    ``(meta, rows)`` where ``meta['engines'][name]`` holds each engine's
    summary and the live registry holds the LAST engine's metrics (the
    snapshot ``export_bench`` writes — paged last, so the checked-in
    metrics block tracks the default engine)."""
    from repro import obs
    from repro.obs import trace as trace_mod
    meta, rows = {}, []
    for engine in engines:
        obs.reset()
        m, _, _ = stream(engine=engine, **kw)
        # fold the flight recorder's per-request reducer into the live
        # registry BEFORE _summary/export snapshot it, so the derived
        # serve.trace.* distributions (queue wait, TTFT wait-vs-prefill,
        # decode stall) land in BENCH_serve.json next to the engine's
        # own aggregates.  The wave engine doesn't emit trace events, so
        # its section simply carries no trace block.
        per = trace_mod.per_request(obs.TRACE.snapshot())
        if per:
            trace_mod.observe(per)
        summ = _summary(m)
        if per:
            summ["trace"] = trace_mod.summary(per)
        meta.setdefault("engines", {})[engine] = summ
        rows.extend(_headline(m, prefix=f"serve_stream[{engine}]"))
        meta.update({k: v for k, v in m.items()
                     if k not in ("engine", "wall_s", "tokens",
                                  "tokens_per_s")})
    return meta, rows


def baseline_p99(doc, model: str | None = None) -> float:
    """Paged p99 e2e from a BENCH_serve doc.  ``model`` reads that
    model's section under ``meta.models``; docs from before multi-model
    runs fall back to the top-level engines block (which priced the
    doc's primary model) and, older still, to the top-level metric
    (which then priced the wave engine)."""
    meta = doc.get("meta", {})
    if model is not None:
        sec = meta.get("models", {}).get(model, {}).get("engines", {})
        p99 = sec.get("paged", {}).get("e2e_p99_us")
        if p99:
            return float(p99)
        if meta.get("model") not in (None, model):
            return 0.0              # baseline never measured this model
    eng = meta.get("engines", {})
    p99 = eng.get("paged", {}).get("e2e_p99_us")
    if p99 is None:
        p99 = doc.get("metrics", {}).get("serve.e2e_us", {}).get("p99")
    return float(p99) if p99 else 0.0


def check_gate(baseline_doc, new_p99: float, model: str | None = None):
    """Returns (ok, message) for the p99-e2e regression gate."""
    tag = f"[{model}] " if model else ""
    old = baseline_p99(baseline_doc, model)
    if not old:
        return True, f"gate: {tag}no baseline p99 — skipped"
    pct = (new_p99 - old) / old * 100.0
    ok = pct <= GATE_PCT
    return ok, (f"gate: {tag}paged e2e p99 {new_p99:.0f}us vs baseline "
                f"{old:.0f}us ({pct:+.1f}%, limit +{GATE_PCT:.0f}%)")


def check_trace_gate(model_name: str = "glm4-9b", retries: int = 2, **kw):
    """Returns (ok, message) for the tracing-overhead gate: paged
    tokens/s with the flight recorder ON must be within
    ``TRACE_GATE_PCT`` of the same stream with it OFF.  A short smoke
    stream's throughput is noisy (one host hiccup skews either side), so
    each side keeps its best over up to ``1 + retries`` attempts and the
    comparison only fails when the traced side loses every time."""
    from repro import obs
    was = obs.TRACE.on
    best = {"on": 0.0, "off": 0.0}
    attempt = 0
    try:
        for attempt in range(1 + retries):
            for mode in ("off", "on"):
                obs.reset()
                obs.TRACE.set_enabled(mode == "on")
                m, _, _ = stream(engine="paged", model_name=model_name,
                                 **kw)
                best[mode] = max(best[mode], m["tokens_per_s"])
            if best["on"] >= best["off"] * (1 - TRACE_GATE_PCT / 100.0):
                break
    finally:
        obs.TRACE.set_enabled(was)
        obs.reset()
    if best["off"] <= 0:
        return True, "trace-gate: no untraced throughput — skipped"
    drop = (best["off"] - best["on"]) / best["off"] * 100.0
    ok = drop <= TRACE_GATE_PCT
    return ok, (f"trace-gate: paged {best['on']:.1f} tok/s traced vs "
                f"{best['off']:.1f} untraced ({drop:+.1f}% drop, limit "
                f"{TRACE_GATE_PCT:.0f}%) [attempts: {attempt + 1}]")


def check_online_gate(model_name: str = "glm4-9b", retries: int = 2, **kw):
    """Returns (ok, message) for the online-tuner overhead gate: paged
    tokens/s with the background re-tuner running must be within
    ``ONLINE_GATE_PCT`` of the same stream without it.

    The gate prices the tuner's *steady state*: one tuner is shared
    across attempts, and an untimed warm pass (a full tuner-on stream,
    then draining ``cycle()`` until nothing re-tunes) pays the one-off
    sweep of the hot classes — candidate compiles included — off the
    clock.  After convergence each cycle is a weigher pass that the
    done-tracker resolves to "no shift, nothing to time", which is what
    a long-lived deployment pays per interval; the cold sweep is a
    bounded one-off (``budget`` timings), not a per-stream tax, so
    gating it against a 2-second smoke stream would only measure the
    smallness of the stream.  Same best-of shape as the trace gate:
    each side keeps its best over up to ``1 + retries`` attempts and
    the comparison only fails when the tuner-on side loses every time
    (a short smoke stream's throughput is noisy; a real regression
    loses every repeat)."""
    from repro import obs
    from repro.tune.online import OnlineTuner
    tuner = OnlineTuner(interval_s=0.3, budget=4, top=1, reps=1,
                        max_dim=512)
    best = {"on": 0.0, "off": 0.0}
    attempt = 0
    try:
        obs.reset()
        stream(engine="paged", model_name=model_name, online=True,
               online_tuner=tuner, **kw)        # warm pass, untimed
        for _ in range(16):                     # drain remaining classes
            if not tuner.cycle().retuned:
                break
        for attempt in range(1 + retries):
            for mode in ("off", "on"):
                obs.reset()
                m, _, _ = stream(engine="paged", model_name=model_name,
                                 online=(mode == "on"),
                                 online_tuner=tuner if mode == "on"
                                 else None, **kw)
                best[mode] = max(best[mode], m["tokens_per_s"])
            if best["on"] >= best["off"] * (1 - ONLINE_GATE_PCT / 100.0):
                break
    finally:
        obs.reset()
    if best["off"] <= 0:
        return True, "online-gate: no tuner-off throughput — skipped"
    drop = (best["off"] - best["on"]) / best["off"] * 100.0
    ok = drop <= ONLINE_GATE_PCT
    return ok, (f"online-gate: paged {best['on']:.1f} tok/s tuner-on vs "
                f"{best['off']:.1f} tuner-off ({drop:+.1f}% drop, limit "
                f"{ONLINE_GATE_PCT:.0f}%) [attempts: {attempt + 1}]")


def run(csv_rows, record: bool = False) -> None:
    """benchmarks/run.py entry: a small stream per engine, headline rows
    only; ``--record`` additionally appends the per-PR trajectory row."""
    from repro import obs
    meta, rows = bench(("wave", "paged"), n_requests=8, rate_hz=4.0,
                       max_new=4)
    csv_rows.extend(rows)
    if record:
        obs.record_trajectory("serve", {"engines": meta["engines"],
                                        "requests": meta["requests"],
                                        "rate_hz": meta["rate_hz"]})


def main() -> None:
    import json
    import pathlib

    from repro import obs
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--engine", default="both",
                    choices=("paged", "wave", "both"))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate-hz", type=float, default=4.0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--model", action="append", default=None,
                    help="smoke arch to stream (repeatable; first one is "
                         "the primary whose engines block tops the "
                         "export; default glm4-9b)")
    ap.add_argument("--policy", default="xla",
                    choices=("xla", "pallas", "auto", "tuned"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gate", action="store_true",
                    help=f"fail when paged e2e p99 regresses more than "
                         f"{GATE_PCT:.0f}%% vs the checked-in "
                         f"BENCH_serve.json")
    ap.add_argument("--record", action="store_true",
                    help="append a per-PR trajectory row to "
                         "BENCH_serve.json")
    ap.add_argument("--no-export", action="store_true",
                    help="print the report without writing BENCH_serve.json")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write the last paged stream's flight-recorder "
                         "timeline as Chrome-trace/Perfetto JSON")
    ap.add_argument("--trace-gate", action="store_true",
                    help=f"fail when tracing costs more than "
                         f"{TRACE_GATE_PCT:.0f}%% paged tokens/s")
    ap.add_argument("--online-tune", action="store_true",
                    help="additionally stream the primary model once "
                         "with the background re-tuner running (cycle/"
                         "swap counts land under meta.online)")
    ap.add_argument("--online-gate", action="store_true",
                    help=f"fail when the online tuner costs more than "
                         f"{ONLINE_GATE_PCT:.0f}%% paged tokens/s")
    ap.add_argument("--online-profile", metavar="PATH", default=None,
                    help="save the profile the --online-tune stream left "
                         "active (the CI artifact)")
    args = ap.parse_args()
    from repro import runtime
    runtime.enable_compile_cache()

    # snapshot the checked-in baseline BEFORE the export overwrites it
    bench_path = obs.bench_root() / "BENCH_serve.json"
    baseline = None
    if args.gate and bench_path.exists():
        baseline = json.loads(pathlib.Path(bench_path).read_text())

    engines = ("wave", "paged") if args.engine == "both" else (args.engine,)
    models = args.model or ["glm4-9b"]
    kw = dict(n_requests=args.requests, rate_hz=args.rate_hz,
              slots=args.slots, max_new=args.max_new, policy=args.policy,
              seed=args.seed)
    meta = None
    for i, mn in enumerate(models):
        m, rows = bench(engines, model_name=mn, **kw)
        if i == 0:
            # primary model keeps the legacy top-level engines block
            meta = m
            meta["models"] = {}
        meta["models"][mn] = {"engines": m["engines"]}
        for name, val, n in rows:
            suffix = f"@{mn}" if len(models) > 1 else ""
            print(f"{name}{suffix}: {val}  (n={n})")
        for engine, s in m["engines"].items():
            print(f"[{mn}:{engine}] {s['tokens']} tokens in {s['wall_s']}s "
                  f"-> {s['tokens_per_s']} tok/s")

    # the forced-xla default (iaat=False) never calls route(), so the
    # tuner's windowed feed would stay empty — online runs promote it
    # to "auto" (input-aware routing, identical on both gate sides so
    # the overhead comparison stays apples-to-apples)
    okw = dict(kw, policy="auto" if args.policy == "xla" else args.policy)

    if args.online_tune:
        obs.reset()
        m, _, _ = stream(engine="paged", model_name=models[0], online=True,
                         online_profile=args.online_profile, **okw)
        meta["online"] = m.get("online", {})
        print(f"[online-tune] {m['tokens_per_s']} tok/s; "
              f"cycles={meta['online'].get('cycles')} "
              f"swaps={meta['online'].get('swaps')}"
              + (f"; profile -> {meta['online']['profile']} "
                 f"({meta['online']['entries']} entries)"
                 if "profile" in meta["online"] else ""))

    if args.trace:
        # the live ring still holds the LAST stream run (the online one
        # when --online-tune — its TUNE_CYCLE/PROFILE_SWAP events land
        # in the timeline — else paged last when --engine both); dump it
        # before the gates re-run anything
        from repro.obs import trace as trace_mod
        tpath = trace_mod.write_trace(args.trace, slots=args.slots)
        print(f"trace: {tpath} ({len(trace_mod.TRACE)} events, "
              f"{trace_mod.TRACE.dropped} dropped; open in "
              f"https://ui.perfetto.dev)")

    if not args.no_export:
        path = obs.export_bench("serve", meta)
        print(f"wrote {path}")
    if args.record:
        obs.record_trajectory("serve", {"engines": meta["engines"],
                                        "models": meta["models"],
                                        "requests": args.requests,
                                        "rate_hz": args.rate_hz})
        print("appended trajectory row")

    failed = False
    if args.gate and "paged" in engines:
        for mn in models:
            sec = meta["models"][mn]["engines"]
            if "paged" not in sec:
                continue
            ok, msg = check_gate(baseline or {},
                                 sec["paged"].get("e2e_p99_us", 0.0), mn)
            # over a short open-loop stream p99 is nearly a max
            # statistic — one host hiccup doubles it — so re-measure
            # before failing; a real capability regression fails every
            # repeat.
            retries = 0
            while not ok and retries < 2:
                retries += 1
                obs.reset()
                m, _, _ = stream(engine="paged", model_name=mn, **kw)
                ok, msg = check_gate(baseline or {},
                                     _summary(m).get("e2e_p99_us", 0.0),
                                     mn)
            print(msg + (f" [retries: {retries}]" if retries else ""))
            failed = failed or not ok
    if args.trace_gate:
        ok, msg = check_trace_gate(model_name=models[0], **kw)
        print(msg)
        failed = failed or not ok
    if args.online_gate:
        ok, msg = check_online_gate(model_name=models[0], **okw)
        print(msg)
        failed = failed or not ok
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
