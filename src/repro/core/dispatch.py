"""The paper's *traditional* (pack-step) GEMM baseline.

The routing brain lives in :mod:`repro.api` (one ``Policy`` + ``Router``
covering every GEMM shape); the deprecation shims that used to forward
the old names (``DispatchConfig``/``configure``/``decide``/``iaat_gemm``)
have been removed — import ``repro.api`` directly.

What remains here is the explicit pack-step pipeline (pad + blocked copy
+ ONE fixed kernel), kept as the paper's §I baseline for the Fig. 3
pack-cost benchmark — it is deliberately NOT routed, which is the point:
it measures what IAAT removes.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro import api
from repro.core import kernelgen, vmem

_PACK_SIG = {"S": (128, 256, 256), "D": (64, 128, 128),
             "C": (64, 128, 128), "Z": (32, 128, 128),
             "H": (256, 256, 256)}


def traditional_gemm(a, b, c=None, alpha=1.0, beta=0.0,
                     trans_a: bool = False, trans_b: bool = False,
                     *, interpret: Optional[bool] = None):
    """Classic block+pack+compute GEMM (paper §I): normalise both operands
    into padded NN layout (the pack step — real extra HBM traffic), then
    run ONE fixed kernel over the padded problem.  Exists to measure what
    IAAT removes."""
    from repro.kernels import iaat_gemm as kmod
    trans = api._trans_str(trans_a, trans_b)
    M, N, K = api._problem_dims(a.shape, b.shape, trans)
    letter = kernelgen.blas_letter(jnp.result_type(a.dtype, b.dtype))
    bm, bn, bk = _PACK_SIG[letter]
    # pack: transpose-normalise + pad to kernel multiples (copies!)
    opa = a.T if trans[0] == "T" else a
    opb = b.T if trans[1] == "T" else b
    Mp, Np, Kp = (vmem.round_up(M, bm), vmem.round_up(N, bn),
                  vmem.round_up(K, bk))
    opa = jnp.pad(opa, ((0, Mp - M), (0, Kp - K)))
    opb = jnp.pad(opb, ((0, Kp - K), (0, Np - N)))
    sig = kernelgen.KernelSig(letter, "NN", bm, bn, bk)
    out = kmod.gemm_region(sig, opa, opb, None, alpha=alpha, beta=0.0,
                           interpret=interpret)[:M, :N]
    if c is not None:
        out = out + jnp.asarray(beta, out.dtype) * c
    return out


def traditional_pack_bytes(M: int, N: int, K: int, dtype) -> int:
    """HBM bytes the pack step moves (read+write both panels)."""
    item = jnp.dtype(dtype).itemsize
    letter = kernelgen.blas_letter(dtype)
    bm, bn, bk = _PACK_SIG[letter]
    Mp, Np, Kp = vmem.round_up(M, bm), vmem.round_up(N, bn), vmem.round_up(K, bk)
    return 2 * (Mp * Kp + Kp * Np) * item
