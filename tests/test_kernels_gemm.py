"""Per-kernel allclose vs ref.py oracle: IAAT GEMM, shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests degrade to skip
from hypothesis import given, settings, strategies as st

from repro import api
from repro.core import dispatch, kernelgen, plan as plan_mod
from repro.kernels import iaat_gemm, ref

jax.config.update("jax_enable_x64", True)

_RTOL = {"S": 2e-5, "D": 1e-12, "C": 2e-4, "Z": 1e-12, "H": 2e-2}


def _mk(rng, shape, letter):
    dt = kernelgen.BLAS_DTYPES.get(letter, jnp.bfloat16)
    x = rng.randn(*shape)
    if letter in ("C", "Z"):
        x = x + 1j * rng.randn(*shape)
    return jnp.asarray(x, dt)


def _run_case(letter, trans, M, N, K, alpha, beta, rng):
    a_shape = (M, K) if trans[0] == "N" else (K, M)
    b_shape = (K, N) if trans[1] == "N" else (N, K)
    a, b = _mk(rng, a_shape, letter), _mk(rng, b_shape, letter)
    c = _mk(rng, (M, N), letter) if beta else None
    with api.using(backend="pallas", interpret=True):
        out = api.gemm(a, b, c, alpha, beta,
                       trans[0] == "T", trans[1] == "T")
    want = ref.ref_gemm(a, b, c, alpha, beta,
                        trans[0] == "T", trans[1] == "T")
    tol = _RTOL[letter]
    np.testing.assert_allclose(np.asarray(out, np.complex128 if letter in
                                          ("C", "Z") else np.float64),
                               np.asarray(want, np.complex128 if letter in
                                          ("C", "Z") else np.float64),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("letter", ["S", "D", "C", "Z"])
@pytest.mark.parametrize("trans", ["NN", "NT", "TN", "TT"])
def test_all_families_small(letter, trans):
    """Paper TABLE I coverage: every (dtype x transposition) family."""
    rng = np.random.RandomState(hash((letter, trans)) % 2**31)
    _run_case(letter, trans, 30, 50, 21, 1.5 if letter in "SD" else 1.5 + 0.5j,
              0.5 if letter in "SD" else 0.25 - 1j, rng)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 140), st.integers(1, 140), st.integers(1, 140),
       st.sampled_from(["NN", "NT", "TN", "TT"]))
def test_sgemm_shape_sweep(M, N, K, trans):
    """Property: planned-kernel GEMM == oracle for arbitrary shapes."""
    rng = np.random.RandomState(M * 10007 + N * 101 + K)
    _run_case("S", trans, M, N, K, 1.0, 0.0, rng)


@pytest.mark.parametrize("M,N,K", [(1, 1, 1), (8, 128, 128), (129, 257, 130),
                                   (5, 3, 200), (512, 512, 512)])
def test_sgemm_edge_shapes(M, N, K):
    rng = np.random.RandomState(0)
    _run_case("S", "NN", M, N, K, 1.0, 0.0, rng)


def test_alpha_beta_fused_epilogue():
    rng = np.random.RandomState(1)
    _run_case("S", "NN", 40, 40, 40, -0.75, 2.5, rng)
    _run_case("Z", "TT", 12, 9, 7, 1 - 2j, -0.5j, rng)


def test_kernel_region_direct():
    """A single generated kernel handles multi-block grids + K tails."""
    rng = np.random.RandomState(2)
    sig = kernelgen.KernelSig("S", "NN", 8, 128, 128)
    a = jnp.asarray(rng.randn(20, 300), jnp.float32)
    b = jnp.asarray(rng.randn(300, 140), jnp.float32)
    out = iaat_gemm.gemm_region(sig, a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               rtol=2e-5, atol=1e-4)


def test_dispatch_large_falls_through_to_xla():
    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randn(600, 600), jnp.float32)
    b = jnp.asarray(rng.randn(600, 600), jnp.float32)
    with api.using(backend="auto", interpret=True):
        assert not api.small_enough(600, 600, 600)
        out = api.gemm(a, b)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.ref_gemm(a, b)), rtol=2e-5,
                               atol=1e-4)


def test_traditional_pack_path_matches():
    rng = np.random.RandomState(4)
    a = jnp.asarray(rng.randn(33, 44), jnp.float32)
    b = jnp.asarray(rng.randn(44, 55), jnp.float32)
    out = dispatch.traditional_gemm(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.ref_gemm(a, b)), rtol=2e-5,
                               atol=1e-4)


def test_plan_region_count_small_problem():
    """Small problems should need very few kernel launches."""
    p = plan_mod.build_plan(64, 128, 64, "S", "NN")
    assert p.num_kernel_calls == 1
    p2 = plan_mod.build_plan(80, 80, 80, "S", "NN")
    assert p2.num_kernel_calls <= 2


@pytest.mark.parametrize("letter,M,N,K", [
    ("H", 4, 200, 300),     # bf16 decode regime: M < bm, K % bk != 0
    ("H", 1, 130, 129),
    ("S", 4, 200, 300),
    ("S", 3, 140, 257),
])
def test_short_m_ragged_k_region(letter, M, N, K):
    """The repaired K-tail mask: a block taller than the operand (M < bm)
    with a ragged K agrees with numpy in bf16 and f32."""
    rng = np.random.RandomState(M * 1000 + K)
    dt = jnp.bfloat16 if letter == "H" else jnp.float32
    sig = kernelgen.KernelSig(letter, "NN", 16 if letter == "H" else 8,
                              128, 128)
    assert M < sig.bm and K % sig.bk
    a = jnp.asarray(rng.randn(M, K), dt)
    b = jnp.asarray(rng.randn(K, N), dt)
    out = iaat_gemm.gemm_region(sig, a, b, interpret=True)
    assert out.dtype == dt
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    tol = _RTOL[letter]
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=tol, atol=tol * np.abs(want).max())
