"""Kernel Generator (paper §IV-B): the install-time stage.

The paper auto-generates *hundreds* of assembly microkernels, one per
(size x dtype x transposition), at install time.  Here a "kernel" is a
``pl.pallas_call`` instance specialised on a :class:`KernelSig`; the
generator enumerates the legal signature table (sizes derived from the VMEM
allocator instead of the NEON register file), and ``build_kernel`` lowers a
signature to a callable.  Built kernels are cached by signature — the
install-time stage in a JIT world is a materialised signature table plus a
build cache that examples/benchmarks can warm eagerly (``install()``).

dtype naming follows BLAS/the paper:
  S = float32, D = float64, C = complex64, Z = complex128
(f64/complex run on TPU via interpret-mode validation; see DESIGN.md for
the hardware demotion policy.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.core import vmem
from repro.core.templates import TRANSPOSITIONS

BLAS_DTYPES = {
    "S": jnp.float32,
    "D": jnp.float64,
    "C": jnp.complex64,
    "Z": jnp.complex128,
}
REAL_OF = {"S": jnp.float32, "D": jnp.float64,
           "C": jnp.float32, "Z": jnp.float64}
IS_COMPLEX = {"S": False, "D": False, "C": True, "Z": True}
# extra dtypes the framework layer uses (not in the paper's BLAS set)
FRAMEWORK_DTYPES = {"H": jnp.bfloat16}


def blas_letter(dtype) -> str:
    d = jnp.dtype(dtype)
    for k, v in {**BLAS_DTYPES, **FRAMEWORK_DTYPES}.items():
        if jnp.dtype(v) == d:
            return k
    raise ValueError(f"unsupported dtype {d}")


@dataclasses.dataclass(frozen=True, order=True)
class KernelSig:
    """Identity of one generated kernel (the paper's TABLE I row entry)."""
    letter: str          # S/D/C/Z/H
    trans: str           # NN/NT/TN/TT
    bm: int
    bn: int
    bk: int

    @property
    def dtype(self):
        return {**BLAS_DTYPES, **FRAMEWORK_DTYPES}[self.letter]

    @property
    def real_dtype(self):
        return REAL_OF.get(self.letter, self.dtype)

    @property
    def complex_(self) -> bool:
        return IS_COMPLEX.get(self.letter, False)

    @property
    def acc_dtype(self):
        return jnp.float64 if self.letter in ("D", "Z") else jnp.float32

    @property
    def name(self) -> str:
        kind = {"S": "sgemm", "D": "dgemm", "C": "cgemm", "Z": "zgemm",
                "H": "hgemm"}[self.letter]
        return f"{kind}_{self.trans.lower()}_{self.bm}x{self.bn}x{self.bk}"

    def footprint(self) -> vmem.Footprint:
        return vmem.footprint(self.bm, self.bn, self.bk, self.real_dtype,
                              complex_=self.complex_,
                              acc_dtype=self.acc_dtype)


# --------------------------------------------------------------------------
# Install-time enumeration.
#
# The paper's table sizes (SGEMM_NN: 16x{1..4}, 12x{1..6}, 8x{1..8},
# 4x{1..13}, ...) fall out of 32 NEON registers.  The TPU table falls out of
# the (sublane, lane) grain and the VMEM budget.  TN gets a reduced table,
# mirroring the paper's observation that TN kernels must be smaller (their
# C-register pressure; for us, the in-VMEM relayout cost of a
# lane-transposed LHS).
# --------------------------------------------------------------------------

_BM_CANDIDATES = (8, 16, 32, 64, 128, 256, 512)
_BN_CANDIDATES = (128, 256, 512)
_BK_CANDIDATES = (128, 256, 512, 1024, 2048)
_TN_BM = (8, 16, 32, 64, 128)
_TN_BN = (128, 256)


@functools.lru_cache(maxsize=None)
def kernel_table(letter: str, trans: str) -> Tuple[KernelSig, ...]:
    """All legal generated kernels for one (dtype, transposition)."""
    if trans not in TRANSPOSITIONS:
        raise ValueError(trans)
    real = REAL_OF.get(letter, FRAMEWORK_DTYPES.get(letter))
    if real is None:
        raise ValueError(letter)
    cx = IS_COMPLEX.get(letter, False)
    bms = _TN_BM if trans == "TN" else _BM_CANDIDATES
    bns = _TN_BN if trans == "TN" else _BN_CANDIDATES
    sub = vmem.sublane(real)
    out: List[KernelSig] = []
    for bm in bms:
        if bm % sub:
            continue
        for bn in bns:
            for bk in _BK_CANDIDATES:
                sig = KernelSig(letter, trans, bm, bn, bk)
                if sig.footprint().fits:
                    # prefer kernels whose accumulator does not spill
                    out.append(sig)
    return tuple(sorted(out))


@functools.lru_cache(maxsize=None)
def full_table() -> Tuple[KernelSig, ...]:
    """The complete install-time kernel census (paper TABLE I analogue)."""
    sigs: List[KernelSig] = []
    for letter in ("S", "D", "C", "Z", "H"):
        for trans in TRANSPOSITIONS:
            sigs.extend(kernel_table(letter, trans))
    return tuple(sigs)


# --------------------------------------------------------------------------
# Build cache: signature -> compiled-callable.
# --------------------------------------------------------------------------

_BUILD_CACHE: Dict[Tuple, Callable] = {}


def build_kernel(sig: KernelSig, *, has_c_in: bool = False,
                 interpret: Optional[bool] = None) -> Callable:
    """Lower one signature to a callable pallas kernel.

    Returned callable computes ``alpha * op(A) @ op(B) + beta * C`` for
    operand shapes that are any multiple of the block size (the grid is
    derived from the actual shapes at call time); edge cells are handled by
    the in-kernel K-mask + Pallas OOB write semantics, NOT by a packed copy.
    """
    from repro.kernels import iaat_gemm  # deferred: kernels import core
    key = (sig, has_c_in, interpret)
    fn = _BUILD_CACHE.get(key)
    if fn is None:
        fn = iaat_gemm.make_gemm_kernel(sig, has_c_in=has_c_in,
                                        interpret=interpret)
        _BUILD_CACHE[key] = fn
    return fn


def install(letters: Sequence[str] = ("S", "D", "C", "Z"),
            trans: Sequence[str] = TRANSPOSITIONS,
            *, interpret: Optional[bool] = None,
            max_per_family: Optional[int] = None,
            tune: bool = False,
            tune_kwargs: Optional[dict] = None) -> int:
    """Eagerly build the kernel table (the install-time stage proper).

    Returns the number of kernels built.  ``max_per_family`` trims each
    (dtype, trans) family for quick installs in tests.  With ``tune=True``
    the build is followed by the empirical sweep (repro.tune): measured
    winners are merged into the persistent DeviceProfile and activated,
    so a subsequent ``configure(backend="tuned")`` dispatch uses them —
    this is the full install-time stage the paper describes, generation
    plus selection.  ``tune_kwargs`` forwards to ``repro.tune.search.sweep``
    (defaults are the quick cube sweep so tests stay fast).
    """
    n = 0
    for letter in letters:
        for tr in trans:
            fam = kernel_table(letter, tr)
            if max_per_family is not None:
                fam = fam[:max_per_family]
            for sig in fam:
                build_kernel(sig, interpret=interpret)
                n += 1
    if tune:
        from repro.tune import profile as profile_mod, search
        kw = dict(cube_only=True, max_dim=128, top=2, reps=3,
                  interpret=interpret)
        kw.update(tune_kwargs or {})
        prof = search.sweep(letters, trans, **kw)
        path = profile_mod.default_profile_path(mode=prof.mode)
        try:
            prof = profile_mod.DeviceProfile.load(path).merge(prof)
        except (OSError, ValueError, KeyError):
            pass        # absent or unusable existing profile: overwrite
        prof.save(path)
        profile_mod.set_active_profile(prof)
    return n


def census() -> Dict[str, int]:
    """Kernel counts per (dtype, trans) — the TABLE I shape of our table."""
    out: Dict[str, int] = {}
    for letter in ("S", "D", "C", "Z", "H"):
        for tr in TRANSPOSITIONS:
            out[f"{letter}GEMM_{tr}"] = len(kernel_table(letter, tr))
    return out
