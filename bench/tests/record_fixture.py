#!/usr/bin/env python3
"""Record the small chip trace that bench/tests/test_xtrace.py reads.

    python3 bench/tests/record_fixture.py --workload glm4-9b.chat_b4 \
        --out bench/tests/fixture.xplane.pb

Runs one traced window of the cell with a half-second slice and keeps
the raw trace; prints the trace's planes and lines for a look by hand
and the reduced numbers the test pins.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="glm4-9b.chat_b4")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--slice", type=float, default=0.5)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness, xtrace
    harness.TRACE_S = args.slice
    harness.KEEP_TRACE = os.path.abspath(args.out)
    cell = harness.load_cell(args.workload)
    out = harness.run_cell(cell, args.seed, args.seconds, True,
                           time.perf_counter())
    print(xtrace.describe(args.out, n=8))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
