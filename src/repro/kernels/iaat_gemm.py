"""The generated IAAT GEMM microkernel family (Pallas TPU).

One ``pl.pallas_call`` instance per :class:`KernelSig` — the TPU analogue
of the paper's auto-generated assembly kernels — named
:func:`call_name` (``iaat_<sig.name>``), so the compiled program and
the device trace name each plan region's kernel:

* operands are consumed in their native (possibly transposed) layout via
  per-transposition BlockSpec index maps + dot_general dimension numbers
  (templates.py) — **no pack step**;
* the K tail is masked in-kernel with an iota predicate and M/N overhang
  is resolved by Pallas's out-of-bounds write clipping — **no scalar
  boundary code**;
* accumulation lives in a VMEM scratch across the (arbitrary) K grid
  dimension, and HBM->VMEM block streaming is double-buffered by the
  Pallas pipeline — the ping-pang operation (paper §IV-B) realised by the
  Mosaic software pipeline instead of hand-interleaved loads;
* complex kernels take/return separate real/imag planes and use the
  3-multiplication Karatsuba template (kernel-optimizer choice; the
  paper's 4-mult fcmla template is kept in templates.py as the baseline).

alpha/beta are baked statically per built kernel (the paper's kernels are
likewise specialised; the dispatch layer falls back to an out-of-kernel
epilogue for traced alpha/beta).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime
from repro.core import templates
from repro.core.kernelgen import KernelSig


def _cdiv(a: int, b: int) -> int:
    return -(a // -b)


def call_name(sig: KernelSig) -> str:
    """The ``pallas_call``'s name: the HLO instruction of the kernel's
    custom call, and the device trace's operation, carry it."""
    return f"iaat_{sig.name}"


# (M, N) blocks are independent; K carries the accumulator.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _a_spec(sig: KernelSig):
    if sig.trans[0] == "N":   # A stored (M, K)
        return pl.BlockSpec((sig.bm, sig.bk), lambda i, j, k: (i, k))
    return pl.BlockSpec((sig.bk, sig.bm), lambda i, j, k: (k, i))


def _b_spec(sig: KernelSig):
    if sig.trans[1] == "N":   # B stored (K, N)
        return pl.BlockSpec((sig.bk, sig.bn), lambda i, j, k: (k, j))
    return pl.BlockSpec((sig.bn, sig.bk), lambda i, j, k: (j, k))


def _c_spec(sig: KernelSig):
    return pl.BlockSpec((sig.bm, sig.bn), lambda i, j, k: (i, j))


def _k_axis(trans_char: str, operand: str) -> int:
    # axis of K in the stored block
    if operand == "a":
        return 1 if trans_char == "N" else 0
    return 0 if trans_char == "N" else 1


def mask_k(x, k_id, bk: int, K: int, axis: int):
    """Zero the K-overhang of a block (guards OOB garbage, incl. NaN/inf).

    Packed dtypes (bf16) select in f32: Mosaic cannot lower a packed
    select when the block is taller than the array (M < bm, the decode
    regime).  The round trip is exact, so the numerics are unchanged."""
    idx = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    keep = idx + k_id * bk < K
    if x.dtype.itemsize >= 4:
        return jnp.where(keep, x, jnp.zeros_like(x))
    return jnp.where(keep, x.astype(jnp.float32), 0.0).astype(x.dtype)


# --------------------------------------------------------------------------
# Real kernel.
# --------------------------------------------------------------------------

def _real_body(sig: KernelSig, nk: int, K: int, alpha, beta, has_c: bool,
               out_dtype, *refs):
    if has_c:
        a_ref, b_ref, c_ref, o_ref, acc_ref = refs
    else:
        a_ref, b_ref, o_ref, acc_ref = refs
        c_ref = None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if K % sig.bk:
        a = mask_k(a, k, sig.bk, K, _k_axis(sig.trans[0], "a"))
        b = mask_k(b, k, sig.bk, K, _k_axis(sig.trans[1], "b"))
    acc_ref[...] += templates.contract(a, b, sig.trans, sig.acc_dtype)

    @pl.when(k == nk - 1)
    def _fin():
        acc = acc_ref[...]
        c_old = c_ref[...] if c_ref is not None else None
        o_ref[...] = templates.epilogue_axpby(acc, c_old, alpha, beta,
                                              out_dtype)


def _real_call(sig: KernelSig, a, b, c, alpha, beta, interpret: bool):
    trans = sig.trans
    M = a.shape[0] if trans[0] == "N" else a.shape[1]
    N = b.shape[1] if trans[1] == "N" else b.shape[0]
    K = a.shape[1] if trans[0] == "N" else a.shape[0]
    gm, gn, nk = _cdiv(M, sig.bm), _cdiv(N, sig.bn), _cdiv(K, sig.bk)
    out_dtype = jnp.result_type(a.dtype, b.dtype)
    has_c = c is not None
    in_specs = [_a_spec(sig), _b_spec(sig)]
    args = [a, b]
    if has_c:
        in_specs.append(_c_spec(sig))
        args.append(c)
    kernel = functools.partial(_real_body, sig, nk, K, alpha, beta, has_c,
                               out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, nk),
        in_specs=in_specs,
        out_specs=_c_spec(sig),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((sig.bm, sig.bn), sig.acc_dtype)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
        name=call_name(sig),
    )(*args)


# --------------------------------------------------------------------------
# Complex kernel (plane-split, Karatsuba accumulation).
# --------------------------------------------------------------------------

def _cx_body(sig: KernelSig, nk: int, K: int, alpha, beta, has_c: bool,
             out_dtype, *refs):
    if has_c:
        (ar_ref, ai_ref, br_ref, bi_ref, cr_ref, ci_ref,
         or_ref, oi_ref, p1_ref, p2_ref, p3_ref) = refs
    else:
        (ar_ref, ai_ref, br_ref, bi_ref,
         or_ref, oi_ref, p1_ref, p2_ref, p3_ref) = refs
        cr_ref = ci_ref = None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        p1_ref[...] = jnp.zeros_like(p1_ref)
        p2_ref[...] = jnp.zeros_like(p2_ref)
        p3_ref[...] = jnp.zeros_like(p3_ref)

    ar, ai = ar_ref[...], ai_ref[...]
    br, bi = br_ref[...], bi_ref[...]
    if K % sig.bk:
        ka = _k_axis(sig.trans[0], "a")
        kb = _k_axis(sig.trans[1], "b")
        ar = mask_k(ar, k, sig.bk, K, ka)
        ai = mask_k(ai, k, sig.bk, K, ka)
        br = mask_k(br, k, sig.bk, K, kb)
        bi = mask_k(bi, k, sig.bk, K, kb)
    p1, p2, p3 = templates.cmul_karatsuba(ar, ai, br, bi, sig.trans,
                                          sig.acc_dtype)
    p1_ref[...] += p1
    p2_ref[...] += p2
    p3_ref[...] += p3

    @pl.when(k == nk - 1)
    def _fin():
        cr_acc, ci_acc = templates.karatsuba_combine(
            p1_ref[...], p2_ref[...], p3_ref[...])
        ar_, ai_ = float(alpha.real), float(alpha.imag)
        outr = ar_ * cr_acc - ai_ * ci_acc
        outi = ar_ * ci_acc + ai_ * cr_acc
        if cr_ref is not None:
            br_, bi_ = float(beta.real), float(beta.imag)
            co_r = cr_ref[...].astype(cr_acc.dtype)
            co_i = ci_ref[...].astype(cr_acc.dtype)
            outr += br_ * co_r - bi_ * co_i
            outi += br_ * co_i + bi_ * co_r
        or_ref[...] = outr.astype(out_dtype)
        oi_ref[...] = outi.astype(out_dtype)


def _cx_call(sig: KernelSig, a, b, c, alpha, beta, interpret: bool):
    trans = sig.trans
    M = a.shape[0] if trans[0] == "N" else a.shape[1]
    N = b.shape[1] if trans[1] == "N" else b.shape[0]
    K = a.shape[1] if trans[0] == "N" else a.shape[0]
    gm, gn, nk = _cdiv(M, sig.bm), _cdiv(N, sig.bn), _cdiv(K, sig.bk)
    real_dtype = sig.real_dtype
    has_c = c is not None
    args = [jnp.real(a).astype(real_dtype), jnp.imag(a).astype(real_dtype),
            jnp.real(b).astype(real_dtype), jnp.imag(b).astype(real_dtype)]
    in_specs = [_a_spec(sig), _a_spec(sig), _b_spec(sig), _b_spec(sig)]
    if has_c:
        args += [jnp.real(c).astype(real_dtype),
                 jnp.imag(c).astype(real_dtype)]
        in_specs += [_c_spec(sig), _c_spec(sig)]
    alpha = complex(alpha)
    beta = complex(beta)
    kernel = functools.partial(_cx_body, sig, nk, K, alpha, beta, has_c,
                               real_dtype)
    outr, outi = pl.pallas_call(
        kernel,
        grid=(gm, gn, nk),
        in_specs=in_specs,
        out_specs=[_c_spec(sig), _c_spec(sig)],
        out_shape=[jax.ShapeDtypeStruct((M, N), real_dtype),
                   jax.ShapeDtypeStruct((M, N), real_dtype)],
        scratch_shapes=[pltpu.VMEM((sig.bm, sig.bn), sig.acc_dtype)] * 3,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
        name=call_name(sig),
    )(*args)
    return lax.complex(outr, outi).astype(sig.dtype)


# --------------------------------------------------------------------------
# Differentiation: pallas_call with scratch has no JVP rule, so the real
# GEMM gets a custom VJP whose backward is itself two GEMMs (the BLAS
# adjoint identities), evaluated through XLA dot (small problems; on TPU
# these would re-enter the IAAT dispatch).
# --------------------------------------------------------------------------

def _adjoints(sig: KernelSig, a, b, dC, alpha):
    ta, tb = sig.trans[0], sig.trans[1]
    opA = a.T if ta == "T" else a
    opB = b.T if tb == "T" else b
    dOpA = alpha * jnp.dot(dC, opB.T,
                           preferred_element_type=jnp.float32)
    dOpB = alpha * jnp.dot(opA.T, dC,
                           preferred_element_type=jnp.float32)
    dA = (dOpA.T if ta == "T" else dOpA).astype(a.dtype)
    dB = (dOpB.T if tb == "T" else dOpB).astype(b.dtype)
    return dA, dB


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _real_region_nc(sig, alpha, beta, interpret, a, b):
    return _real_call(sig, a, b, None, alpha, beta, interpret)


def _real_region_nc_fwd(sig, alpha, beta, interpret, a, b):
    return _real_region_nc(sig, alpha, beta, interpret, a, b), (a, b)


def _real_region_nc_bwd(sig, alpha, beta, interpret, res, dC):
    a, b = res
    return _adjoints(sig, a, b, dC.astype(jnp.float32), alpha)


_real_region_nc.defvjp(_real_region_nc_fwd, _real_region_nc_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _real_region_c(sig, alpha, beta, interpret, a, b, c):
    return _real_call(sig, a, b, c, alpha, beta, interpret)


def _real_region_c_fwd(sig, alpha, beta, interpret, a, b, c):
    return _real_region_c(sig, alpha, beta, interpret, a, b, c), (a, b)


def _real_region_c_bwd(sig, alpha, beta, interpret, res, dC):
    a, b = res
    dA, dB = _adjoints(sig, a, b, dC.astype(jnp.float32), alpha)
    return dA, dB, (beta * dC.astype(jnp.float32)).astype(dC.dtype)


_real_region_c.defvjp(_real_region_c_fwd, _real_region_c_bwd)


# --------------------------------------------------------------------------
# Public builders.
# --------------------------------------------------------------------------

def gemm_region(sig: KernelSig, a, b, c=None, *, alpha=1.0, beta=0.0,
                interpret: Optional[bool] = None):
    """Run one plan region: op(a) @ op(b) (+ beta*c) with kernel ``sig``.

    Operand shapes may be any size; the grid is derived with ceil-div and
    edges are masked as described in the module docstring.  Real dtypes
    are differentiable (custom VJP); complex kernels are forward-only
    (the paper's C/Z BLAS entries are not training paths).
    ``interpret=None`` decides from the platform
    (:func:`repro.runtime.pallas_interpret`)."""
    interpret = runtime.pallas_interpret(interpret)
    if sig.complex_:
        return _cx_call(sig, a, b, c, alpha, beta, interpret)
    if c is None:
        return _real_region_nc(sig, float(alpha), float(beta), interpret,
                               a, b)
    return _real_region_c(sig, float(alpha), float(beta), interpret,
                          a, b, c)


def make_gemm_kernel(sig: KernelSig, *, has_c_in: bool = False,
                     interpret: Optional[bool] = None):
    """Install-time build: returns the specialised kernel callable."""
    def call(a, b, c=None, alpha=1.0, beta=0.0):
        if has_c_in and c is None:
            raise ValueError(f"{sig.name} built with has_c_in")
        return gemm_region(sig, a, b, c, alpha=alpha, beta=beta,
                           interpret=interpret)
    call.__name__ = sig.name
    call.sig = sig
    return call
