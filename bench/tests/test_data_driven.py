"""A configuration, a mix, a cell and a per-layer metric added as files
are found by name, with no edit to the harness; one mix driven through
PagedEngine on a tiny configuration gives the counts it should."""
import time

import numpy as np

from bench import harness, traffic
from bench.tests.conftest import add_files

SEED = 2**31 + 977


def test_new_files_are_found_by_name(bench_copy):
    cell_name = add_files(bench_copy, "ssm")
    cell = harness.load_cell(cell_name, bench_copy)
    assert cell.config["config"]["d_model"] == 64
    assert cell.mix["engine"]["slots"] == 4
    assert cell.fixed["rate_rps"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["finished.tiny"]
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "tpot_p90_ms", "setup_s"}
    cfg = harness.model_config(cell)
    assert (cfg.n_layers, cfg.d_model, cfg.ssm.d_state) == (2, 64, 16)


def test_mix_through_paged_engine_counts(bench_copy):
    cell = harness.load_cell(add_files(bench_copy, "dense"), bench_copy)
    ses = harness.open_session(cell, SEED)
    run, plan = harness.serve(ses, cell, SEED, 3.0, False,
                              time.perf_counter())
    n = traffic.n_requests(cell.mix, cell.fixed, 3.0)
    assert len(run.reqs) == len(plan.prompts) == n == 9
    assert all(r.finish is not None for r in run.reqs)
    assert sum(r.n_out for r in run.reqs) == int(np.sum(plan.max_new))
    for r in run.reqs:
        assert len(ses.eng.done[r.rid]) == plan.max_new[r.rid]
    # every decode GEMM of the family at M = slots was routed, and at
    # these tiny sizes the Router sends each one to Pallas
    decode = {(dims[-2], dims[-1]): d.use_pallas
              for (op, _l, _t, dims), (_c, d) in run.routes.items()
              if op == "matmul" and tuple(dims[:-2]) == (4, 1)}
    want = {tuple(g) for g in ses.family.layer_gemms(ses.shape)}
    want |= {(ses.shape.d_model, ses.eng.model.cfg.vocab_padded)}
    assert set(decode) == want
    assert all(decode.values())
    got = harness.read_metrics(run, cell.per_layer)
    assert got == {"finished.tiny": {"value": 9.0, "unit": "requests"}}
