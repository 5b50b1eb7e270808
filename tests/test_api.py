"""repro.api: the unified Policy + Router covering every GEMM shape.

Covers the PR-2 acceptance criteria:
* route() source precedence (forced > profile > analytical) per op kind,
* ND matmul shape/grad parity vs jnp.matmul (including under jax.vmap),
* DeviceProfile entries demonstrably changing the blocks grouped GEMM
  uses (vs the analytical pick_blocks fallback when no profile exists),
* the XLA/pallas epilogues agreeing on the output dtype for any c dtype,
* the traditional (pack-step) baseline agreeing with the routed path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import Decision, Policy, Router
from repro.core import dispatch
from repro.core.kernelgen import KernelSig
from repro.kernels import grouped_gemm, ops
from repro.models import common
from repro.tune import classes, profile as profile_mod
from repro.tune.profile import DeviceProfile, ProfileEntry
from repro.tune.timer import Measurement


@pytest.fixture(autouse=True)
def _isolated_profile_state(tmp_path, monkeypatch):
    monkeypatch.setenv(profile_mod.CACHE_ENV, str(tmp_path / "cache"))
    profile_mod.clear_active_profile()
    yield
    profile_mod.clear_active_profile()


def _entry(pallas_us, xla_us, sig=KernelSig("S", "NN", 64, 128, 128)):
    m = lambda us: Measurement(us, us * 0.9, us * 1.1, 3)  # noqa: E731
    return ProfileEntry(sig, m(pallas_us), m(xla_us))


def _activate(M, N, K, pallas_us, xla_us, sig, letter="S", trans="NN"):
    prof = DeviceProfile(profile_mod.current_device_kind())
    prof.record(classes.size_class(M, N, K, letter, trans),
                _entry(pallas_us, xla_us, sig=sig))
    profile_mod.set_active_profile(prof)
    return prof


# -- Policy ----------------------------------------------------------------

def test_policy_is_frozen_and_replaceable():
    p = Policy()
    with pytest.raises(dataclasses.FrozenInstanceError):
        p.backend = "xla"
    assert p.replace(backend="xla").backend == "xla"
    assert p.backend == "auto"


def test_policy_kernel_family_derivation():
    assert Policy(backend="xla").kind == "xla"
    assert not Policy(backend="xla").pallas
    for b in ("auto", "pallas", "tuned"):
        assert Policy(backend=b).pallas
    # explicit pin beats derivation (the old two-axis Backend)
    assert Policy(backend="auto", kernels="xla").kind == "xla"


def test_ambient_policy_install_and_using():
    base = api.current_policy()
    try:
        api.install(Policy(backend="tuned", method="greedy"))
        assert api.current_policy().backend == "tuned"
        with api.using(backend="xla"):
            assert api.current_policy().backend == "xla"
            assert api.current_policy().method == "greedy"  # layered
        assert api.current_policy().backend == "tuned"
    finally:
        api.install(base)


def test_named_policy_covers_cli_surface():
    assert api.named_policy("xla") == common.XLA
    assert api.named_policy("pallas") == common.PALLAS
    assert api.named_policy("tuned").backend == "tuned"
    with pytest.raises(ValueError):
        api.named_policy("cuda")


# -- Router: precedence per op kind ----------------------------------------

@pytest.mark.parametrize("op,dims", [
    ("gemm", (45, 45, 45)),
    ("matmul", (3, 15, 45, 45)),
    ("batched_gemm", (8, 45, 45, 45)),
    ("ragged_gemm", (8, 128, 45, 45)),
])
def test_route_source_precedence(op, dims):
    sig = KernelSig("S", "NN", 32, 128, 256)
    # the profile class keyed by the per-group/2-D problem of `dims`
    if op == "gemm":
        M, N, K = dims
    elif op == "matmul":
        M, N, K = dims[0] * dims[1], dims[-1], dims[-2]
    else:
        M, N, K = dims[1], dims[3], dims[2]
    _activate(M, N, K, pallas_us=1.0, xla_us=100.0, sig=sig)

    forced = api.route(op, dims, "S", policy=Policy(backend="pallas"))
    assert forced.source == "forced" and forced.use_pallas
    assert api.route(op, dims, "S",
                     policy=Policy(backend="xla")).source == "forced"
    prof = api.route(op, dims, "S", policy=Policy(backend="tuned"))
    assert prof.source == "profile" and prof.use_pallas
    assert prof.sig == sig
    profile_mod.clear_active_profile()
    ana = api.route(op, dims, "S", policy=Policy(backend="tuned"))
    assert ana.source == "analytical"       # tuned degrades, never strands
    assert ana == api.route(op, dims, "S", policy=Policy(backend="auto"))
    assert ana.op == op                     # source inspectable per op kind


def test_route_profile_says_xla_wins():
    _activate(45, 45, 45, pallas_us=100.0, xla_us=1.0,
              sig=KernelSig("S", "NN", 32, 128, 256))
    d = api.route("gemm", (45, 45, 45), "S", policy=Policy(backend="tuned"))
    assert d.source == "profile" and not d.use_pallas


@pytest.mark.parametrize("backend", ["pallas", "auto"])
def test_plan_overflow_is_a_visible_decision(backend):
    """A Pallas decision whose plan exceeds ``max_plan_regions`` becomes
    its own XLA decision: counted, logged in ROUTES, numerically XLA."""
    from repro import obs
    dims = (1, 6448, 1536)                      # a 3-region bf16 plan
    pol = Policy(backend=backend, interpret=True)
    assert api.route("gemm", dims, "H", policy=pol).use_pallas
    obs.ROUTES.reset()
    before = obs.counter("route.plan_overflow").value
    tight = pol.replace(max_plan_regions=2)
    d = api.route("gemm", dims, "H", policy=tight)
    assert (d.use_pallas, d.source) == (False, "plan_overflow")
    assert obs.counter("route.plan_overflow").value == before + 1
    rows = obs.ROUTES.snapshot()
    assert [(r["use_pallas"], r["source"]) for r in rows] == \
        [(False, "plan_overflow")]
    rng = np.random.RandomState(7)
    a = jnp.asarray(rng.randn(2, 300), jnp.float32)
    b = jnp.asarray(rng.randn(300, 70), jnp.float32)
    out = api.gemm(a, b, policy=pol.replace(max_plan_regions=0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               rtol=2e-5, atol=1e-4)


def test_policy_interpret_follows_platform():
    """Unset, interpret mode is the platform's call: on in this CPU
    process, and an explicit flag still wins."""
    from repro import runtime
    assert Policy().interpret is None
    assert api.named_policy("auto").interpret is None
    assert runtime.pallas_interpret(None) is (jax.default_backend() != "tpu")
    assert runtime.pallas_interpret(False) is False
    assert runtime.pallas_interpret(True) is True


def test_compile_cache_dir_prefers_environment(monkeypatch, tmp_path):
    from repro import runtime
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert runtime.compile_cache_dir() == str(runtime.CACHE_DIR)
    assert runtime.CACHE_DIR.name == ".jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.compile_cache_dir() == str(tmp_path)


def test_route_rejects_unknown_op():
    with pytest.raises(ValueError):
        api.route("conv", (4, 4, 4), "S")


def test_router_pins_policy():
    r = Router(Policy(backend="xla"))
    assert r.route("gemm", (8, 8, 8), "S").source == "forced"
    # an unpinned Router follows the ambient policy
    with api.using(backend="pallas"):
        assert Router().route("gemm", (8, 8, 8), "S").use_pallas


# -- grouped block selection: profile-steered vs analytical -----------------

def test_batched_gemm_blocks_profile_vs_fallback():
    """The acceptance check: a DeviceProfile entry demonstrably changes
    the blocks batched_gemm uses; without one, pick_blocks decides."""
    G, C, K, N = 4, 45, 200, 300
    analytical = grouped_gemm.pick_blocks(C, K, N, jnp.float32)
    no_prof = api.route("batched_gemm", (G, C, K, N), jnp.float32,
                        policy=Policy(backend="tuned"))
    assert no_prof.source == "analytical"
    assert no_prof.blocks == analytical

    sig = KernelSig("S", "NN", 16, 256, 512)
    assert (sig.bm, sig.bn, sig.bk) != analytical
    _activate(C, N, K, pallas_us=1.0, xla_us=100.0, sig=sig)
    tuned = api.route("batched_gemm", (G, C, K, N), jnp.float32,
                      policy=Policy(backend="tuned"))
    assert tuned.source == "profile"
    assert tuned.blocks == (sig.bm, sig.bn, sig.bk)
    assert tuned.blocks != no_prof.blocks

    # and the executor actually computes the right thing with them
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(G, C, K), jnp.float32)
    w = jnp.asarray(rng.randn(G, K, N), jnp.float32)
    out = api.batched_gemm(x, w, policy=Policy(backend="tuned"))
    np.testing.assert_allclose(np.asarray(out),
                               np.einsum("gck,gkn->gcn", np.asarray(x),
                                         np.asarray(w)),
                               rtol=2e-4, atol=2e-3)


def test_profile_changes_blocks_the_kernel_actually_uses(monkeypatch):
    """End-to-end acceptance: the blocks handed to the Pallas grouped
    kernels (not just the route() answer) flip when a profile appears."""
    G, C, K, N = 2, 45, 200, 300
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(G, C, K), jnp.float32)
    w = jnp.asarray(rng.randn(G, K, N), jnp.float32)
    seen = []
    real = grouped_gemm.batched_gemm

    def spy(x, w, *, interpret=True, blocks=None):
        seen.append(blocks)
        return real(x, w, interpret=interpret, blocks=blocks)

    monkeypatch.setattr(grouped_gemm, "batched_gemm", spy)
    pol = Policy(backend="tuned")
    api.batched_gemm(x, w, policy=pol)          # no profile: analytical
    sig = KernelSig("S", "NN", 16, 256, 512)
    _activate(C, N, K, pallas_us=1.0, xla_us=100.0, sig=sig)
    api.batched_gemm(x, w, policy=pol)          # profile: measured blocks
    assert seen[0] == grouped_gemm.pick_blocks(C, K, N, jnp.float32)
    assert seen[1] == (sig.bm, sig.bn, sig.bk)
    assert seen[0] != seen[1]

    # ragged path: same flip, row block pinned
    seen_r = []
    real_r = grouped_gemm.ragged_gemm

    def spy_r(x, w, gids, *, bm=128, interpret=True, blocks=None):
        seen_r.append(blocks)
        return real_r(x, w, gids, bm=bm, interpret=interpret, blocks=blocks)

    monkeypatch.setattr(grouped_gemm, "ragged_gemm", spy_r)
    bm = 128
    xr = jnp.asarray(rng.randn(G * bm, K), jnp.float32)
    gids = jnp.asarray([0, 1], jnp.int32)
    profile_mod.clear_active_profile()
    api.ragged_gemm(xr, w, gids, bm=bm, policy=pol)
    _activate(bm, N, K, pallas_us=1.0, xla_us=100.0, sig=sig)
    api.ragged_gemm(xr, w, gids, bm=bm, policy=pol)
    assert seen_r[0] == (bm,) + grouped_gemm.pick_blocks(
        bm, K, N, jnp.float32)[1:]
    assert seen_r[1] == (bm, sig.bn, sig.bk)
    assert seen_r[0] != seen_r[1]


def test_ragged_gemm_blocks_keep_caller_row_block():
    G, bm, K, N = 4, 128, 200, 300
    sig = KernelSig("S", "NN", 16, 256, 512)
    _activate(bm, N, K, pallas_us=1.0, xla_us=100.0, sig=sig)
    d = api.route("ragged_gemm", (G, bm, K, N), jnp.float32,
                  policy=Policy(backend="tuned"))
    assert d.source == "profile"
    assert d.blocks == (bm, sig.bn, sig.bk)   # bm pinned: sizes are traced


def test_ops_batched_gemm_resolves_blocks_via_router():
    """kernels.ops grouped entries consult the router when blocks=None."""
    G, C, K, N = 2, 16, 32, 128
    sig = KernelSig("S", "NN", 8, 128, 128)
    _activate(C, N, K, pallas_us=1.0, xla_us=100.0, sig=sig)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(G, C, K), jnp.float32)
    w = jnp.asarray(rng.randn(G, K, N), jnp.float32)
    with api.using(backend="tuned"):
        out = ops.batched_gemm(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.einsum("gck,gkn->gcn", np.asarray(x),
                                         np.asarray(w)),
                               rtol=2e-4, atol=2e-3)


def test_grouped_xla_fallbacks_match_einsum():
    G, C, K, N = 3, 16, 24, 40
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(G, C, K), jnp.float32)
    w = jnp.asarray(rng.randn(G, K, N), jnp.float32)
    pol = Policy(backend="xla")
    out = api.batched_gemm(x, w, policy=pol)
    np.testing.assert_allclose(np.asarray(out),
                               np.einsum("gck,gkn->gcn", np.asarray(x),
                                         np.asarray(w)),
                               rtol=1e-5, atol=1e-5)
    # ragged xla fallback: 2 groups x bm rows each
    bm = 8
    xr = jnp.asarray(rng.randn(2 * bm, K), jnp.float32)
    gids = jnp.asarray([0, 1], jnp.int32)
    outr = api.ragged_gemm(xr, w[:2], gids, bm=bm, policy=pol)
    want = np.concatenate([np.asarray(xr[:bm]) @ np.asarray(w[0]),
                           np.asarray(xr[bm:]) @ np.asarray(w[1])])
    np.testing.assert_allclose(np.asarray(outr), want, rtol=1e-5)
    # and the pallas path agrees with the fallback
    outp = api.ragged_gemm(xr, w[:2], gids, bm=bm,
                           policy=Policy(backend="pallas"))
    np.testing.assert_allclose(np.asarray(outp), want, rtol=2e-4,
                               atol=2e-3)


# -- ND matmul: shape + grad parity, vmap-safety ----------------------------

@pytest.mark.parametrize("lead", [(), (4,), (2, 3), (2, 2, 2)])
def test_matmul_nd_parity(lead):
    rng = np.random.RandomState(0)
    K, N = 24, 40
    x = jnp.asarray(rng.randn(*lead, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    for pol in (Policy(backend="pallas"), Policy(backend="auto"),
                Policy(backend="xla")):
        out = api.matmul(x, w, policy=pol)
        assert out.shape == lead + (N,)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.matmul(x, w)),
                                   rtol=2e-4, atol=2e-4)


def test_matmul_grad_parity():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 5, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 24), jnp.float32)
    pol = Policy(backend="pallas", interpret=True)

    def f_iaat(x, w):
        return jnp.sum(api.matmul(x, w, policy=pol) ** 2)

    def f_ref(x, w):
        return jnp.sum(jnp.matmul(x, w) ** 2)

    gx, gw = jax.grad(f_iaat, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(f_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=2e-4, atol=2e-3)


def test_matmul_under_vmap():
    rng = np.random.RandomState(4)
    xs = jnp.asarray(rng.randn(6, 5, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 24), jnp.float32)
    pol = Policy(backend="pallas", interpret=True)
    out = jax.vmap(lambda x: api.matmul(x, w, policy=pol))(xs)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.matmul(xs, w)),
                               rtol=2e-4, atol=2e-4)
    # vmap-of-grad, the training shape
    g = jax.vmap(jax.grad(
        lambda x: jnp.sum(api.matmul(x, w, policy=pol) ** 2)))(xs)
    gr = jax.vmap(jax.grad(
        lambda x: jnp.sum(jnp.matmul(x, w) ** 2)))(xs)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-4, atol=2e-3)


def test_matmul_iaat_false_bypasses_router():
    x = jnp.ones((3, 4, 8), jnp.float32)
    w = jnp.ones((8, 16), jnp.float32)
    out = api.matmul(x, w, policy=Policy(backend="pallas", iaat=False))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.matmul(x, w)))


# -- epilogue dtype agreement (satellite) -----------------------------------

@pytest.mark.parametrize("c_dtype", [jnp.float32, jnp.bfloat16])
def test_xla_and_pallas_epilogue_dtype_agree(c_dtype):
    """beta*c with a c of ANY dtype must not promote/demote the output,
    and beta must apply at accumulator precision (NOT c.dtype — the old
    XLA epilogue cast beta into bf16 when c was bf16): both epilogues
    cast c into the accumulator, then to result_type(a, b)."""
    rng = np.random.RandomState(5)
    alpha, beta = 1.5, 0.3            # 0.3 is inexact in bf16
    a = jnp.asarray(rng.randn(16, 12), jnp.float32)
    b = jnp.asarray(rng.randn(12, 20), jnp.float32)
    c = jnp.asarray(rng.randn(16, 20), c_dtype)
    out_x = api.gemm(a, b, c, alpha=alpha, beta=beta,
                     policy=Policy(backend="xla"))
    out_p = api.gemm(a, b, c, alpha=alpha, beta=beta,
                     policy=Policy(backend="pallas", interpret=True))
    assert out_x.dtype == jnp.result_type(a.dtype, b.dtype)
    assert out_p.dtype == out_x.dtype
    want = (alpha * np.asarray(a, np.float64) @ np.asarray(b, np.float64)
            + beta * np.asarray(c.astype(jnp.float32), np.float64))
    np.testing.assert_allclose(np.asarray(out_x), want, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_p), want, rtol=1e-4,
                               atol=1e-4)


# -- post-shim surface ------------------------------------------------------

def test_shims_are_gone():
    """PR-6 housekeeping: the deprecation shims were removed for real."""
    from repro.kernels import ops
    for mod, name in ((dispatch, "DispatchConfig"), (dispatch, "configure"),
                      (dispatch, "decide"), (dispatch, "iaat_gemm"),
                      (common, "Backend"), (ops, "gemm_jit")):
        assert not hasattr(mod, name), f"{mod.__name__}.{name} still exists"


def test_traditional_baseline_matches_routed_path():
    """The surviving dispatch module is the pack-step baseline only, and
    it agrees numerically with the routed pallas path."""
    rng = np.random.RandomState(7)
    a = jnp.asarray(rng.randn(24, 16), jnp.float32)
    b = jnp.asarray(rng.randn(16, 20), jnp.float32)
    trad = dispatch.traditional_gemm(a, b, interpret=True)
    routed = api.gemm(a, b, policy=Policy(backend="pallas", interpret=True))
    np.testing.assert_allclose(np.asarray(trad), np.asarray(routed),
                               rtol=2e-5, atol=1e-4)
    assert dispatch.traditional_pack_bytes(45, 77, 33, jnp.float32) > 0


def test_mm_uses_ambient_policy():
    x = jnp.ones((2, 3, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    with api.using(backend="xla", iaat=False):
        out = common.mm(x, w)            # no explicit be: ambient policy
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.matmul(x, w)))
