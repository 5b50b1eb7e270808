"""Candidate search: analytical prior first, stopwatch second.

Per size class the search (a) enumerates every legal kernel from the
install-time table, (b) ranks them with the roofline prior — padded-grid
FLOPs vs streamed HBM traffic, the same physics as ``cost.py`` — and
(c) micro-benchmarks only the ``top`` ranked candidates plus the XLA
baseline.  The prior never *decides*, it only prunes: tritonBLAS uses
its analytical model the same way, as a prior that measurements refine,
which keeps sweep cost O(top) per class instead of O(|table|) while the
final word stays empirical.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, runtime
from repro.core import cost, kernelgen
from repro.core.kernelgen import KernelSig
from repro.tune import classes as classes_mod
from repro.tune.classes import SizeClass
from repro.tune.profile import DeviceProfile, ProfileEntry, current_device_kind
from repro.tune.timer import Measurement, try_measure


def _cdiv(a: int, b: int) -> int:
    return -(a // -b)


def prior_us(sig: KernelSig, M: int, N: int, K: int) -> float:
    """Roofline estimate (µs) of running the whole problem on one kernel.

    Compute counts the *padded* grid (an oversized block wastes MXU work on
    masked lanes); traffic counts actual per-grid-step panel streaming plus
    the C write-out.  Absolute scale is napkin math; only the ordering is
    consumed, and only as a pruning prior.
    """
    gm, gn, nk = _cdiv(M, sig.bm), _cdiv(N, sig.bn), _cdiv(K, sig.bk)
    item = jnp.dtype(sig.real_dtype).itemsize
    planes = 2 if sig.complex_ else 1
    mults = 3 if sig.complex_ else 1      # karatsuba
    flops = 2.0 * (gm * sig.bm) * (gn * sig.bn) * (nk * sig.bk) * mults
    traffic = (gm * gn * nk * (sig.bm * sig.bk + sig.bk * sig.bn)
               + 2.0 * M * N) * item * planes
    peak = cost.PEAK_FLOPS_F32 / (2 if sig.letter in ("D", "Z") else 1)
    return max(flops / peak, traffic / cost.HBM_BW) * 1e6


def candidates(letter: str, trans: str, M: int, N: int, K: int,
               top: int = 4) -> List[KernelSig]:
    """The ``top`` analytically-promising kernels for this problem."""
    table = kernelgen.kernel_table(letter, trans)
    ranked = sorted(table, key=lambda s: (prior_us(s, M, N, K), s))
    return list(ranked[:max(1, top)])


# --------------------------------------------------------------------------
# Benchmark one size class.
# --------------------------------------------------------------------------

def _operands(sc: SizeClass, M: int, N: int, K: int):
    rng = np.random.RandomState(0x1AA7)
    dt = {**kernelgen.BLAS_DTYPES, **kernelgen.FRAMEWORK_DTYPES}[sc.letter]
    a_shape = (M, K) if sc.trans[0] == "N" else (K, M)
    b_shape = (K, N) if sc.trans[1] == "N" else (N, K)

    def mk(shape):
        x = rng.randn(*shape)
        if kernelgen.IS_COMPLEX.get(sc.letter, False):
            x = x + 1j * rng.randn(*shape)
        return jnp.asarray(x, dt)

    return mk(a_shape), mk(b_shape)


def _xla_fn(trans: str, a, b) -> Callable[[], jax.Array]:
    @jax.jit
    def f(a, b):
        opa = a.T if trans[0] == "T" else a
        opb = b.T if trans[1] == "T" else b
        return jnp.dot(opa, opb)
    return lambda: f(a, b)


def _pallas_fn(sig: KernelSig, a, b,
               interpret: Optional[bool]) -> Callable[[], jax.Array]:
    from repro.kernels import iaat_gemm

    @jax.jit
    def f(a, b):
        return iaat_gemm.gemm_region(sig, a, b, None, alpha=1.0, beta=0.0,
                                     interpret=interpret)
    return lambda: f(a, b)


def tune_class(sc: SizeClass, *, top: int = 4, warmup: int = 1,
               reps: int = 5, interpret: Optional[bool] = None
               ) -> ProfileEntry:
    """Measure one size class at its representative shape; returns the
    entry (best pallas sig + both timings) to record in the profile."""
    M, N, K = classes_mod.representative(sc)
    a, b = _operands(sc, M, N, K)
    xla = try_measure(_xla_fn(sc.trans, a, b), warmup=warmup, reps=reps)
    best_sig: Optional[KernelSig] = None
    best: Optional[Measurement] = None
    for sig in candidates(sc.letter, sc.trans, M, N, K, top=top):
        m = try_measure(_pallas_fn(sig, a, b, interpret),
                        warmup=warmup, reps=reps)
        if m is not None and (best is None or m.median_us < best.median_us):
            best_sig, best = sig, m
    return ProfileEntry(best_sig, best, xla)


def tune_grouped_class(sc: SizeClass, *, G: int = 4, top: int = 4,
                       warmup: int = 1, reps: int = 5,
                       interpret: Optional[bool] = None) -> ProfileEntry:
    """Measure one grouped size class ON the grouped kernel.

    The per-group problem (C, K, N) keys the same class table as 2-D
    gemm (M = C), but G problems stream through one ``batched_gemm``
    launch, so its crossover and best blocks differ from a lone gemm of
    the same shape — this times the real thing instead of reusing the
    2-D entry (the PR-2 leftover).  The XLA side is the batched einsum
    the executor falls back to.
    """
    from repro.kernels import grouped_gemm as _gg
    C, N, K = classes_mod.representative(sc)
    rng = np.random.RandomState(0x1AA7)
    dt = {**kernelgen.BLAS_DTYPES, **kernelgen.FRAMEWORK_DTYPES}[sc.letter]

    def mk(shape):
        x = rng.randn(*shape)
        if kernelgen.IS_COMPLEX.get(sc.letter, False):
            x = x + 1j * rng.randn(*shape)
        return jnp.asarray(x, dt)

    x, w = mk((G, C, K)), mk((G, K, N))

    @jax.jit
    def _einsum(x, w):
        return jnp.einsum("gck,gkn->gcn", x, w)

    xla = try_measure(lambda: _einsum(x, w), warmup=warmup, reps=reps)
    best_sig: Optional[KernelSig] = None
    best: Optional[Measurement] = None
    for sig in candidates(sc.letter, "NN", C, N, K, top=top):

        def _fn(sig=sig):
            @jax.jit
            def f(x, w):
                return _gg.batched_gemm(x, w, interpret=interpret,
                                        blocks=(sig.bm, sig.bn, sig.bk))
            return lambda: f(x, w)

        m = try_measure(_fn(), warmup=warmup, reps=reps)
        if m is not None and (best is None or m.median_us < best.median_us):
            best_sig, best = sig, m
    return ProfileEntry(best_sig, best, xla)


# --------------------------------------------------------------------------
# Budgeted sweep — the online tuner's entry point.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TuneTarget:
    """One class the online tuner wants re-timed, with its traffic
    weight.  ``kind`` picks the measuring harness: ``"gemm"`` times the
    2-D plan path, ``"grouped"`` times ``batched_gemm`` and records
    under the profile's ``grouped:`` key namespace."""
    kind: str                       # "gemm" | "grouped"
    sc: SizeClass
    weight: float = 0.0


def budgeted_sweep(targets: Sequence[TuneTarget], *, budget: int = 8,
                   top: int = 1, warmup: int = 0, reps: int = 1,
                   interpret: Optional[bool] = None, grouped_G: int = 4,
                   device_kind: Optional[str] = None,
                   ) -> Tuple[DeviceProfile, List[TuneTarget], int]:
    """Re-tune ``targets`` in order until the timing budget runs out.

    ``budget`` caps the number of stopwatch timings per call (each class
    costs at most ``1 + top``: the baseline plus the prior-pruned pallas
    candidates) so one online cycle's worth of measuring is bounded no
    matter how many classes went hot.  Stops BEFORE starting a class
    that could exceed the budget — a class is either fully timed or not
    touched.  Returns ``(delta_profile, tuned_targets, timings_spent)``;
    the delta holds only the classes actually tuned, ready to merge.
    """
    interpret = runtime.pallas_interpret(interpret)
    prof = DeviceProfile(device_kind or current_device_kind(),
                         mode="interpret" if interpret else "compiled")
    per_class = 1 + max(1, top)
    spent = 0
    tuned: List[TuneTarget] = []
    with obs.span("tune.online_sweep"):
        for t in targets:
            if spent + per_class > budget:
                break
            with obs.span("tune.class"):
                if t.kind == "grouped":
                    entry = tune_grouped_class(
                        t.sc, G=grouped_G, top=top, warmup=warmup,
                        reps=reps, interpret=interpret)
                    prof.record_grouped(
                        t.sc, dataclasses.replace(entry, origin="online"))
                else:
                    entry = tune_class(t.sc, top=top, warmup=warmup,
                                       reps=reps, interpret=interpret)
                    prof.record(
                        t.sc, dataclasses.replace(entry, origin="online"))
            obs.counter("tune.classes_swept").inc()
            spent += per_class
            tuned.append(t)
    return prof, tuned, spent


def sweep(letters: Sequence[str] = ("S",),
          trans: Sequence[str] = ("NN",), *,
          min_dim: int = 8, max_dim: int = 512, cube_only: bool = False,
          top: int = 4, warmup: int = 1, reps: int = 5,
          interpret: Optional[bool] = None,
          device_kind: Optional[str] = None,
          progress: Optional[Callable[[SizeClass, ProfileEntry], None]] = None,
          ) -> DeviceProfile:
    """Run the tuning sweep and return the (unsaved) DeviceProfile."""
    interpret = runtime.pallas_interpret(interpret)
    prof = DeviceProfile(device_kind or current_device_kind(),
                         mode="interpret" if interpret else "compiled")
    with obs.span("tune.sweep"):
        for sc in classes_mod.classes_up_to(letters, trans, max_dim,
                                            min_dim=min_dim,
                                            cube_only=cube_only):
            with obs.span("tune.class"):
                entry = tune_class(sc, top=top, warmup=warmup, reps=reps,
                                   interpret=interpret)
            obs.counter("tune.classes_swept").inc()
            prof.record(sc, entry)
            if progress is not None:
                progress(sc, entry)
    return prof
