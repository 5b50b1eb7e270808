"""End-to-end behaviour tests for the paper's system.

Covers the full IAAT pipeline (install-time table -> run-time plan ->
kernel execution plan -> routing) and its integration into the model
stack (a pallas Policy routes model matmuls through the paper's path).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro import api
from repro.core import kernelgen, plan as plan_mod
from repro.kernels import ref
from repro.models import registry
from repro.models.common import PALLAS, XLA

KEY = jax.random.PRNGKey(0)


def test_install_then_plan_then_execute():
    """The paper's full two-stage flow on one problem."""
    n = kernelgen.install(letters=("S",), trans=("NN",), interpret=True,
                          max_per_family=10)
    assert n == 10
    p = plan_mod.build_plan(45, 77, 33, "S", "NN")
    assert p.num_kernel_calls >= 1
    assert p.memops() > 0
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(45, 33), jnp.float32)
    b = jnp.asarray(rng.randn(33, 77), jnp.float32)
    out = plan_mod.execute(p, a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(a) @ np.asarray(b),
                               rtol=2e-5, atol=1e-4)


def test_plan_cache_repeated_calls():
    """'IAAT fits the situation where computes matrix multiplication with
    the same size repeatedly' — the plan is built once per signature."""
    plan_mod.build_plan.cache_clear()
    p1 = plan_mod.build_plan(33, 44, 55, "S", "NT")
    p2 = plan_mod.build_plan(33, 44, 55, "S", "NT")
    assert p1 is p2
    info = plan_mod.build_plan.cache_info()
    assert info.hits >= 1


def test_iaat_gemm_under_jit():
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(24, 36), jnp.float32)
    b = jnp.asarray(rng.randn(36, 48), jnp.float32)

    @jax.jit
    def f(a, b):
        with api.using(backend="pallas", interpret=True):
            return api.gemm(a, b)

    np.testing.assert_allclose(np.asarray(f(a, b)),
                               np.asarray(a) @ np.asarray(b),
                               rtol=2e-5, atol=1e-4)


def test_iaat_gemm_differentiable():
    """The planned path is differentiable (needed for training use)."""
    rng = np.random.RandomState(2)
    a = jnp.asarray(rng.randn(16, 24), jnp.float32)
    b = jnp.asarray(rng.randn(24, 32), jnp.float32)

    def loss(a, b):
        with api.using(backend="pallas", interpret=True):
            return jnp.sum(api.gemm(a, b) ** 2)

    ga = jax.grad(loss)(a, b)
    ga_ref = jax.grad(lambda a, b: jnp.sum((a @ b) ** 2))(a, b)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ga_ref),
                               rtol=2e-4, atol=2e-3)


def test_model_forward_through_iaat_backend():
    """A whole smoke model runs with every matmul routed through IAAT
    dispatch + pallas-interpret kernels, matching the XLA backend."""
    import dataclasses
    cfg = dataclasses.replace(configs.get_smoke("olmo-1b"), dtype="float32")
    model = registry.build(cfg)
    params = model.init(KEY)
    tok = jax.random.randint(KEY, (1, 16), 0, cfg.vocab)
    l_xla, _ = model.forward_train(params, {"tokens": tok}, XLA)
    l_iaat, _ = model.forward_train(params, {"tokens": tok},
                                    PALLAS)
    scale = float(jnp.abs(l_xla).max())
    assert float(jnp.abs(l_xla - l_iaat).max()) / scale < 5e-3


def test_moe_through_pallas_batched_gemm():
    """MoE expert compute through the batched small-GEMM kernel."""
    import dataclasses
    cfg = dataclasses.replace(configs.get_smoke("moonshot-v1-16b-a3b"),
                              dtype="float32")
    model = registry.build(cfg)
    params = model.init(KEY)
    tok = jax.random.randint(KEY, (1, 16), 0, cfg.vocab)
    l_xla, _ = model.forward_train(params, {"tokens": tok}, XLA)
    be = PALLAS.replace(backend="pallas", iaat=False)
    l_pl, _ = model.forward_train(params, {"tokens": tok}, be)
    scale = float(jnp.abs(l_xla).max())
    assert float(jnp.abs(l_xla - l_pl).max()) / scale < 5e-3


def test_dispatch_thresholds_route_correctly():
    with api.using(paper_thresholds=True):
        cfg = api.current_policy()
        assert cfg.threshold("NN") == 80
        assert cfg.threshold("TN") == 32
    cfg = api.current_policy()
    assert cfg.threshold("NN") == 80 * api.TPU_SCALE
