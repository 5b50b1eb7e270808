"""Serving launcher: continuous-batched generation with a smoke model.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
        --requests 8 --max-new 16

Every decoder-only family serves through the paged slot-level engine
(attention K/V in the block pool, recurrent carries in per-slot state
rows); there is no wave fallback any more.  ``--engine auto`` is kept
as an alias for ``paged`` so existing invocations don't break, and the
``serve.engine_fallback`` counter records how often a family misses
the paged path (asserted 0 in tests for every registry family).
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro import api, configs, obs, runtime
from repro.models.registry import build as build_model
from repro.serve import PagedEngine, Request

log = logging.getLogger("repro.serve")


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="auto",
                    choices=("auto", "paged"))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--backend", default="xla",
                    choices=list(api.POLICY_NAMES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write the flight-recorder timeline as a "
                         "Chrome-trace/Perfetto JSON after the run")
    ap.add_argument("--online-tune", action="store_true",
                    help="run the background traffic-aware re-tuner for "
                         "the engine's lifetime: hot size classes from "
                         "ROUTES.windowed() are re-timed on a budget and "
                         "merged into the live profile (kill switch: "
                         "REPRO_ONLINE_TUNE=0; pair with a routing "
                         "--backend — forced xla never calls route(), "
                         "so the tuner sees no traffic and idles)")
    args = ap.parse_args()
    runtime.enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    if cfg.family in ("encdec", "audio"):
        raise SystemExit("use a decoder-only arch for the serve demo")
    model = build_model(cfg)
    if model.paged_decode is None:
        # should be unreachable for any decoder-only registry family;
        # the counter is asserted 0 in tests so a regression that
        # reopens the engine split cannot land silently
        obs.counter("serve.engine_fallback").inc()
        raise SystemExit(f"--engine paged: family {cfg.family!r} has no "
                         f"paged serving path")
    # model-entry policy install: the engine snapshots the ambient policy
    be = api.install(api.named_policy(args.backend))
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.RandomState(args.seed)
    tuner = None
    if args.online_tune:
        from repro.tune.online import OnlineTuner
        # small-budget knobs: a smoke serve is short, so cycle fast and
        # time little — the point is the loop, not the profile quality
        tuner = OnlineTuner(interval_s=0.5, budget=4, top=1, reps=1)
    batcher = PagedEngine(model, params, be, slots=args.slots,
                          max_len=256, temperature=args.temperature,
                          seed=args.seed, block_size=args.block_size,
                          tuner=tuner)
    log.info("engine=paged arch=%s slots=%d online_tune=%s", args.arch,
             args.slots, bool(tuner))
    t0 = time.time()
    for rid in range(args.requests):
        plen = int(rng.randint(4, 24))
        prompt = rng.randint(0, cfg.vocab, plen).astype(np.int32)
        batcher.submit(Request(rid, prompt, max_new=args.max_new))
    done = batcher.run()
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in done.values())
    for rid in sorted(done):
        log.info("req %d -> %d tokens: %s...", rid, len(done[rid]),
                 done[rid][:8])
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens / dt:.1f} tok/s)")
    if tuner is not None:
        print(f"online tuner: {tuner.cycles} cycles, {tuner.swaps} "
              f"profile swaps")
    if args.trace:
        from repro.obs import trace as trace_mod
        path = trace_mod.write_trace(args.trace, slots=args.slots)
        print(f"trace: {path} ({len(trace_mod.TRACE)} events, "
              f"{trace_mod.TRACE.dropped} dropped; open in "
              f"https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
