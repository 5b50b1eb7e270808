"""The trace reduction and the kernel roofline, on a trace recorded on
the chip: half a second of glm4-9b.chat_b4 on one TPU v5e
(``fixture.xplane.pb.gz``, from ``record_fixture.py``)."""
import json
import pathlib
import types

import pytest

from bench import readers, roofline, xtrace

HERE = pathlib.Path(__file__).resolve().parent
PEAK = json.loads((HERE.parent / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]

KV = ('%closed_call.24 = bf16[4,256]{1,0:T(4,128)(2,1)S(1)} custom-call('
      'bf16[4,4096]{1,0:T(4,128)(2,1)S(1)} %fusion.180, bf16[4096,256]'
      '{1,0:T(8,128)(2,1)} %fusion.184), custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def run():
    red = xtrace.reduce(str(HERE / "fixture.xplane.pb.gz"))
    return types.SimpleNamespace(trace=red)


def test_programs_and_kernels(run):
    lo, hi = run.trace.window
    assert 0.5 < (hi - lo) * 1e-9 < 0.6
    dec = readers.programs(run, readers.DECODE)
    assert len(dec) == 24
    assert len(readers.programs(run, readers.PREFILL)) == 14
    assert 13.0 < readers.program_ms(run, readers.DECODE) < 14.0
    # two k/v projections in each of the 4 layers of every decode step
    kern = readers.kernel_events(run, readers.DECODE)
    assert len(kern) == 8 * len(dec)
    assert all(roofline.kernel_cost(k[0]) == (2 * 4 * 4096 * 256, 0)
               for k in kern)


def test_busy_and_idle(run):
    lo, hi = run.trace.window
    busy = xtrace.total(xtrace.busy(run.trace))
    assert 0 < busy <= hi - lo
    gaps = dict(xtrace.idle_gaps(run.trace, [run.trace.window]))
    assert abs(sum(gaps.values()) - (hi - lo - busy) * 1e-9) < 1e-6
    assert set(gaps) <= {"host:none", "host:step", "host:drain",
                         "host:prefill_chunk", "host:decode_issue",
                         "host:wait"}
    top = xtrace.top_ops(run.trace)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0


def test_kernel_roofline(run):
    kern = readers.kernel_events(run, readers.DECODE)
    need = sum(roofline.kernel_seconds(k[0], PEAK) for k in kern)
    took = sum(b - a for _n, a, b in kern) * 1e-9
    assert 0 < need / took <= 1.0


def test_kernel_cost_counts_hbm_operands_only():
    flops, nbytes = roofline.kernel_cost(KV)
    assert flops == 2 * 4 * 4096 * 256
    assert nbytes == 4096 * 256 * 2          # B is in HBM; A and C in VMEM
    with pytest.raises(ValueError):
        roofline.kernel_cost(KV.replace("bf16[4096,256]", "bf16[4000,256]"))


def test_module_names_match_exactly():
    red = xtrace.Reduced((0, 1), [("jit_decode(123)", 0, 1),
                                  ("jit_decode_x(9)", 0, 1),
                                  ("jit_decode", 0, 1)], [], [])
    assert len(red.module_intervals("jit_decode")) == 2
