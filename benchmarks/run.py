# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# Exits nonzero when any suite reports an ERROR row (CI regression gate).
# Serving is measured on the chip by ``bench/run.py``, not here.
from __future__ import annotations

import os
import sys
import time

# runnable as `python benchmarks/run.py` from anywhere: put the repo root
# (for `benchmarks.*`) and src/ (for `repro.*`, when not pip-installed)
# on the path ourselves.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    from benchmarks import (gemm_sweep, kernel_table, pack_cost,
                            tiling_memops, tune_report)
    suites = [
        ("tiling_memops", tiling_memops.run),   # paper Fig. 2
        ("pack_cost", pack_cost.run),           # paper Fig. 3
        ("kernel_table", kernel_table.run),     # paper TABLE I
        ("gemm_sweep", gemm_sweep.run),         # paper Figs. 4-7
        ("tune_report", tune_report.run),       # empirical vs analytical
    ]
    if "--quick" in sys.argv[1:]:
        quick = {"tiling_memops", "kernel_table", "tune_report"}
        suites = [s for s in suites if s[0] in quick]
    rows = []
    for name, fn in suites:
        t0 = time.perf_counter()
        try:
            fn(rows)
            rows.append((f"{name}/suite_s", (time.perf_counter() - t0) * 1e6,
                         "ok"))
        except Exception as e:  # noqa: BLE001 — report and continue
            rows.append((f"{name}/suite_s", (time.perf_counter() - t0) * 1e6,
                         f"ERROR:{type(e).__name__}:{e}"))
    print("name,us_per_call,derived")
    bad = 0
    for name, us, derived in rows:
        print(f"{name},{us},{derived}")
        if isinstance(derived, str) and derived.startswith("ERROR"):
            bad += 1
    if bad:
        sys.exit(1)


if __name__ == "__main__":
    main()
