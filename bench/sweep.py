#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: one engine, one window per offered
rate and seed, to find the highest rate the system sustains.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 1,2,3 --seeds 1,2,3

A rate is sustained when the median over the seeds of the drain, the
seconds from the window's close until the last of its requests finished,
is at most ``SUSTAINED_DRAIN_S``: about what the longest request of the
chat mix (256 output tokens at some 20 ms, after its prefill) takes
alone, so that a longer drain means requests still queued at the close.
The knee is the highest rate at which it and every lower rate swept are
sustained.  Prints one JSON line per rate and seed (the end-to-end
metrics, the drain, TTFT and queue-wait p90), then one line with each
rate's median drain, the knee and 0.8 x the knee.  It never compares
outputs; bench/run.py does that.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: Largest median drain, in seconds, of a sustained rate.
SUSTAINED_DRAIN_S = 6.0


def knee(drains: dict) -> dict:
    """Median drain per rate, the knee and the cell rate from
    ``{rate: [drain per seed]}``; an unfinished request drains forever."""
    med = {r: statistics.median(d) for r, d in sorted(drains.items())}
    best = None
    for r, m in med.items():
        if m > SUSTAINED_DRAIN_S:
            break
        best = r
    return {"median_drain_s": med, "knee_rps": best,
            "cell_rate_rps": None if best is None else round(0.8 * best, 3)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    from bench import harness, readers
    cell = harness.load_cell(args.workload)
    if cell.mix["loop"] != "open":
        print("sweep: only an open loop has an offered rate", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    ses = harness.open_session(cell, seeds[0])
    drains = {}
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, fixed={**cell.fixed, "rate_rps": rate})
        for seed in seeds:
            run, plan = harness.serve(ses, c, seed, args.seconds, False,
                                      time.perf_counter())
            reqs = run.window_reqs()
            close = run.t0 + run.seconds
            fins = [r.finish for r in reqs if r.finish is not None]
            drain = (max(fins) - close if len(fins) == len(reqs)
                     else float("inf"))
            drains.setdefault(rate, []).append(drain)
            ttft = [(r.first or close + harness.DRAIN_S) - r.arrival
                    for r in reqs]
            print(json.dumps({
                "rate_rps": rate, "seed": seed, "requests": len(reqs),
                "unfinished": len(reqs) - len(fins), "drain_s": drain,
                "tokens_per_s": run.tokens_in_window / run.seconds,
                "offered_tokens_per_s": float(sum(plan.max_new))
                / run.seconds,
                "ttft_p90_ms": readers.p90_ms(ttft),
                "queue_wait_p90_ms": (readers.p90_ms(readers.queue_wait_s(run))
                                      if len(fins) == len(reqs) else None),
                "tpot_p90_ms": readers.p90_ms(readers.tpot_s(run)),
                "generator_late_max_s": max(run.lateness, default=0.0),
                "compiles_in_window": run.compiles_in_window}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "sustained_drain_s": SUSTAINED_DRAIN_S,
                      **knee(drains)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
