"""Kernel Executing Plan (paper §V-B).

After the input-aware tile algorithm produces a :class:`Tiling`, the plan
builder fuses maximal runs of identical blocks into *regions* (one
``pallas_call`` grid each) and binds every region to a generated kernel
signature from the install-time table.  Executing the plan = running the
region kernels and stitching their outputs — no pack step, no boundary
scalar code.

Plans are cached by the full problem signature, which is the paper's
"repeated same-size GEMM" sweet spot: the first call plans, every
subsequent call (and every jit retrace with the same shapes) reuses the
plan for free.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax.numpy as jnp
from jax import lax

from repro.core import kernelgen, vmem
from repro.core.kernelgen import KernelSig
from repro.core.tiler import Block, Tiling, tile_tpu


@dataclasses.dataclass(frozen=True)
class Region:
    """A (gm x gn) grid of identical (bm x bn) kernel blocks."""
    sig: KernelSig
    m0: int
    n0: int
    gm: int
    gn: int

    @property
    def m_extent(self) -> int:
        return self.gm * self.sig.bm

    @property
    def n_extent(self) -> int:
        return self.gn * self.sig.bn


@dataclasses.dataclass(frozen=True)
class Plan:
    M: int
    N: int
    K: int
    letter: str
    trans: str
    regions: Tuple[Region, ...]
    tiling: Tiling

    @property
    def num_kernel_calls(self) -> int:
        return len(self.regions)

    def memops(self) -> int:
        return self.tiling.memops(self.K)


def _choose_bk(letter: str, trans: str, bm: int, bn: int, K: int) -> int:
    """Largest table bk that fits VMEM with (bm, bn); capped near K."""
    sig0 = kernelgen.kernel_table(letter, trans)
    cands = sorted({s.bk for s in sig0 if s.bm == bm and s.bn == bn})
    if not cands:
        raise ValueError(f"no kernel {letter}/{trans} {bm}x{bn}")
    ka = vmem.align_k(K, kernelgen.REAL_OF.get(letter, jnp.bfloat16))
    # smallest bk covering K in one step, else largest available (more k
    # reuse per C block residency = fewer acc spills).
    for bk in cands:
        if bk >= ka:
            return bk
    return cands[-1]


def _override_plan(M: int, N: int, K: int, letter: str, trans: str,
                   sig: KernelSig) -> Plan:
    """Single-region plan pinned to a tuned kernel signature.

    The empirical tuner (repro.tune) measures whole-problem kernels, so a
    profile override is one ceil-div grid of ``sig`` blocks covering C;
    M/N overhang is resolved by the kernels' masking exactly as in tiled
    plans."""
    if sig.letter != letter or sig.trans != trans:
        raise ValueError(f"override {sig.name} does not match "
                         f"{letter}/{trans}")
    gm = -(M // -sig.bm)
    gn = -(N // -sig.bn)
    blocks = []
    for i in range(gm):
        m0 = i * sig.bm
        for j in range(gn):
            n0 = j * sig.bn
            blocks.append(Block(m0, n0, min(sig.bm, M - m0),
                                min(sig.bn, N - n0)))
    tiling = Tiling(M, N, tuple(blocks), "tuned")
    return Plan(M, N, K, letter, trans,
                (Region(sig, 0, 0, gm, gn),), tiling)


@functools.lru_cache(maxsize=4096)
def build_plan(M: int, N: int, K: int, letter: str, trans: str,
               method: str = "dp",
               override: Optional[KernelSig] = None) -> Plan:
    if override is not None:
        return _override_plan(M, N, K, letter, trans, override)
    tiling = tile_tpu(M, N, letter, trans, method)
    # fuse: per stripe, merge equal-width runs; then merge vertically
    # adjacent stripes with identical runs.
    rows: List[Tuple[int, int, List[Tuple[int, int, int]]]] = []
    by_row: dict = {}
    for b in tiling.blocks:
        by_row.setdefault((b.m0, b.m), []).append(b)
    for (m0, m), blocks in sorted(by_row.items()):
        blocks.sort(key=lambda b: b.n0)
        runs: List[Tuple[int, int, int]] = []  # (n0, n, count)
        for b in blocks:
            if runs and runs[-1][1] == b.n and \
                    runs[-1][0] + runs[-1][1] * runs[-1][2] == b.n0:
                n0, n, c = runs[-1]
                runs[-1] = (n0, n, c + 1)
            else:
                runs.append((b.n0, b.n, 1))
        rows.append((m0, m, runs))
    merged: List[Tuple[int, int, int, List[Tuple[int, int, int]]]] = []
    for m0, m, runs in rows:
        if merged and merged[-1][1] == m and merged[-1][3] == runs \
                and merged[-1][0] + merged[-1][1] * merged[-1][2] == m0:
            p0, pm, pc, pruns = merged[-1]
            merged[-1] = (p0, pm, pc + 1, pruns)
        else:
            merged.append((m0, m, 1, runs))
    regions: List[Region] = []
    for m0, m, gm, runs in merged:
        for n0, n, gn in runs:
            bk = _choose_bk(letter, trans, m, n, K)
            regions.append(Region(KernelSig(letter, trans, m, n, bk),
                                  m0, n0, gm, gn))
    return Plan(M, N, K, letter, trans, tuple(regions), tiling)


# --------------------------------------------------------------------------
# Execution.
# --------------------------------------------------------------------------

def _slice_operand(x, lo: int, hi: int, axis: int):
    idx = [slice(None), slice(None)]
    idx[axis] = slice(lo, hi)
    return x[tuple(idx)]


def execute(plan: Plan, a, b, c=None, alpha=1.0, beta=0.0, *,
            interpret: Optional[bool] = None):
    """Run the kernel executing plan; returns C (M x N)."""
    from repro.kernels import iaat_gemm
    M, N, K, trans = plan.M, plan.N, plan.K, plan.trans
    out_dtype = jnp.result_type(a.dtype, b.dtype)
    out = jnp.zeros((M, N), out_dtype) if len(plan.regions) > 1 or \
        plan.regions[0].m_extent < M or plan.regions[0].n_extent < N or \
        plan.regions[0].m0 or plan.regions[0].n0 else None
    a_m_axis = 0 if trans[0] == "N" else 1
    b_n_axis = 1 if trans[1] == "N" else 0
    result = None
    for r in plan.regions:
        m_lo, m_hi = r.m0, min(M, r.m0 + r.m_extent)
        n_lo, n_hi = r.n0, min(N, r.n0 + r.n_extent)
        if m_lo >= M or n_lo >= N:
            continue  # fully-overhang region (alignment padding)
        a_sl = _slice_operand(a, m_lo, m_hi, a_m_axis)
        b_sl = _slice_operand(b, n_lo, n_hi, b_n_axis)
        c_sl = None
        if c is not None:
            c_sl = c[m_lo:m_hi, n_lo:n_hi]
        blk = iaat_gemm.gemm_region(r.sig, a_sl, b_sl, c_sl,
                                    alpha=alpha, beta=beta,
                                    interpret=interpret)
        if out is None:
            result = blk
        else:
            out = lax.dynamic_update_slice(out, blk.astype(out_dtype),
                                           (m_lo, n_lo))
    return result if out is None else out
