"""The control at a size a test run holds: the reference computed with
float8 matmul operands, put in the program's place and read at the served
positions of a sound tiny run, goes through the cell's own comparison and
comes out not correct, where the program on the same run is correct.
(bench/control.py reads the same numbers on the chip at each cell's own
size; PERF.md gives those readings.)"""
import time

import pytest

from bench import harness
from bench.tests.conftest import add_files


@pytest.mark.parametrize("family", ["ssm", "dense"])
def test_control_fails_where_the_program_passes(bench_copy, family):
    limit = 0.05
    cell = harness.load_cell(add_files(bench_copy, family, limit=limit),
                             bench_copy)
    out = harness.run_cell(cell, 99, 3.0, False, time.perf_counter(),
                           control=True, log=lambda *a, **k: None)
    prog = out["program_checks"]["max_logit_gap"]["value"]
    ctl = out["checks"]["max_logit_gap"]
    print(family, prog, ctl)
    assert out["program_correct"], out["program_checks"]
    assert out["correct"] is False, out["checks"]
    assert ctl["limit"] == limit
    assert prog <= limit < ctl["value"]
