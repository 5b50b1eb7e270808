"""Operations and bytes of a GEMM kernel call, and the least time the
chip needs for it (its roofline), from the call's own device event.

The bytes are those the kernel moves to or from HBM.  On the serving
path XLA often stages a kernel's operands in the core's own memory
(VMEM) with a copy of its own before the kernel runs; the kernel's
device event then names each operand with its memory space (``S(1)`` in
the layout), and an operand staged there costs the kernel no HBM bytes.
"""
from __future__ import annotations

import math
import re

#: Bytes per element by HLO type name.
HLO_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "s32": 4,
                "u32": 4, "pred": 1}
_TYPED = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")


def _operand(m):
    dims = [int(d) for d in m.group(2).split(",") if d]
    in_vmem = "S(1)" in (m.group(3) or "")
    return dims, HLO_ITEMSIZE[m.group(1)], in_vmem


def kernel_cost(op: str):
    """(operations, HBM bytes) of one GEMM kernel call from its device
    event's HLO text: ``C[M,N] = custom-call(A[M,K], B[K,N], ...)``."""
    head, _, rest = op.partition(" custom-call(")
    args = rest.split("), custom_call_target", 1)[0]
    out = _operand(_TYPED.search(head.split(" = ", 1)[1]))
    ins = [_operand(m) for m in _TYPED.finditer(args)]
    (m_, k_), (k2, n_) = ins[0][0], ins[1][0]
    if k_ != k2 or out[0] != [m_, n_]:
        raise ValueError(f"not a GEMM kernel call: {op[:200]}")
    flops = 2 * m_ * k_ * n_
    nbytes = sum(math.prod(d) * size for d, size, vmem in ins + [out]
                 if not vmem)
    return flops, nbytes


def kernel_seconds(op: str, peak: dict) -> float:
    flops, nbytes = kernel_cost(op)
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
