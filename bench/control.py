#!/usr/bin/env python3
"""Readings that set the limit of ``max_logit_gap``: for each seed, one
run of the cell (set-up, a window at the cell's own load, the check) in
which the control stands in the program's place.  The control is the
reference computed with float8 matmul operands; at each served position
it reads the gap of the token that it puts first, and that number goes
through the cell's own comparison and limits.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

Prints one JSON line per seed: the control's gap and whether the line
with the control in the program's place is correct (it has to be
false), beside the program's own gap and verdict on the same run.  The
benchmark's own runs never run the control.  For glm4-9b give one seed
per process: on one TPU v5e a second seed in the same process ran out of
device memory.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    from bench import harness
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), control=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control_gap": out["checks"]["max_logit_gap"]["value"],
            "control_correct": out["correct"],
            "program_gap": out["program_checks"]["max_logit_gap"]["value"],
            "program_correct": out["program_correct"],
            "limit": out["checks"]["max_logit_gap"]["limit"],
            "metrics": out["metrics"],
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
