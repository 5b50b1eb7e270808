"""Kernels: the IAAT GEMM kernels' share of their roofline in the decode
step, in %: over the kernel calls inside decode steps in the traced slice,
the least time the chip needs for each (the larger of its operations over
peak bf16 FLOP/s and its HBM bytes over HBM bandwidth, from the shapes
and memory spaces in the call's HLO text), over their device time.
Nothing to read when the Router sent no decode GEMM to Pallas."""
from bench import readers, roofline, xtrace


def read(run):
    routed = any(op == "matmul" and tuple(dims[:-2]) == (run.slots, 1)
                 and decision.use_pallas
                 for (op, _l, _t, dims), (_c, decision) in run.routes.items())
    if not routed:
        return None
    kern = readers.kernel_events(run, readers.DECODE)
    if not kern:
        raise xtrace.TraceError("the Router sent decode GEMMs to Pallas but "
                                "no kernel operation matches "
                                f"{readers.KERNEL_RE.pattern!r}")
    need = sum(roofline.kernel_seconds(name, run.peak) for name, _a, _b in kern)
    took = sum(b - a for _n, a, b in kern) * 1e-9
    return 100.0 * need / took
