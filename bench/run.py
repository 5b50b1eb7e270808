#!/usr/bin/env python3
"""Benchmark of the serving path: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model and weights from the seed, serves its traffic mix
through ``PagedEngine`` for ``--seconds`` after warming up every shape,
checks the served tokens against the plain reference, and prints one JSON
line last: ``correct``, ``attempted``, ``failed``, ``metrics`` (end to end
with ``--trace 0``, per layer with ``--trace 1``), ``device`` and, traced,
``breakdown``; the numbers compared and their limits come last, under
``checks``, and again as the last lines of standard error.

It runs where it is started and never falls back: without a TPU, with
fewer chips than the cell asks for, or outside a checkout of the
repository it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: {ROOT} is not a checkout of the repository "
              "(no src/repro)", file=sys.stderr)
        return 2
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    cell = harness.load_cell(args.workload)

    import jax
    devices = jax.devices()
    print(f"setup: to the devices {time.perf_counter() - T_START} s",
          file=sys.stderr)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s). No "
              "fallback.", file=sys.stderr)
        return 3
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
