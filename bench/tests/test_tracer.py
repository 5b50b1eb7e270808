"""The traced slice is the end of the window: the tracer takes its
schedule from the window's own start (a schedule taken before the window
opened traced a slice at its very beginning), and it runs on past the
close until a decode step and a prefill chunk were issued inside it.  A
metric listed for the cell that reads nothing fails the run."""
import time

import pytest

from bench import harness, xtrace
from bench.tests.conftest import add_files


def test_slice_ends_the_window(bench_copy, monkeypatch):
    cell = harness.load_cell(add_files(bench_copy, "ssm"), bench_copy)
    # no TPU here: the CPU trace has no device plane to reduce
    monkeypatch.setattr(xtrace, "reduce",
                        lambda path: xtrace.Reduced((0.0, 1.0), [], [], []))
    monkeypatch.setattr(harness, "_trace_summary", lambda run: None)
    ses = harness.open_session(cell, 5)
    run, _plan = harness.serve(ses, cell, 5, 6.0, True, time.perf_counter())
    start = run.clock[1] - run.t0
    assert 6.0 - harness.TRACE_S <= start < 6.0
    t = run.clock[1]
    assert any(d[0] >= t for d in run.decode_issues)
    assert any(p >= t for p in run.prefill_issues)


def test_metric_that_reads_nothing_fails(bench_copy):
    cell = harness.load_cell(add_files(bench_copy, "ssm"), bench_copy)
    (bench_copy / "bench" / "metrics" / "finished.tiny.py").write_text(
        "def read(run):\n    return None\n")
    run = harness.Run(cell=cell, seconds=1.0, seed=0)
    with pytest.raises(harness.MetricMissing):
        harness.read_metrics(run, cell.per_layer)
