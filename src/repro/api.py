"""One routing API: ``Policy`` + ``Router`` for every GEMM shape.

The paper's thesis is that *one* input-aware decision layer should pick
the kernel for every small GEMM.  This module is that layer:

* :class:`Policy` — one frozen config merging the old
  ``dispatch.DispatchConfig`` (backend / interpret / method / thresholds)
  and ``models.common.Backend`` (kernel family / iaat flag).  There is
  exactly one ambient policy (a contextvar, installed once at model
  entry with :func:`install` or scoped with :func:`using`) and every
  entry point takes a per-call ``policy=`` override — no more
  re-entering a context manager on every projection.

* :class:`Router` — generalises the 2-D ``decide()`` to an op-shaped
  ``route(op, dims, dtype) -> Decision`` covering ``gemm`` (2-D BLAS),
  ``matmul`` (ND, leading batch dims, vmap-safe), ``batched_gemm``
  (equal-capacity grouped) and ``ragged_gemm`` (group-contiguous rows).
  Grouped block selection flows through the Decision: the measured
  DeviceProfile entry for the per-group (C, K, N) problem when one
  exists (``backend="tuned"``), the analytical ``pick_blocks`` table
  lookup otherwise — so ``repro.tune`` profiles steer the MoE
  expert-FFN and serving decode paths, not just the 2-D entry.

Decision precedence, uniform across ops (DESIGN.md §Policy & Router):

    forced (backend="pallas"/"xla")  >  profile (backend="tuned")
                                     >  analytical (smallness criterion)

Executors (:func:`gemm`, :func:`matmul`, :func:`batched_gemm`,
:func:`ragged_gemm`) act on the Decision so callers never branch on
backend themselves.  (The pre-Policy entry points — ``dispatch.iaat_gemm``,
``dispatch.configure``, ``models.common.Backend``, ``ops.gemm_jit`` —
are gone; the migration table lives in DESIGN.md §Policy & Router.)

Every ``route`` call is recorded in :data:`repro.obs.ROUTES` — the
shape histogram that seeds traffic-aware tuning — and memoized through
the same entry (see ``Router.route``).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import kernelgen, paper_table, plan as plan_mod

# TPU scale factor for the smallness thresholds: the paper's 80/32 bounds
# are where pack+boundary overheads stop mattering on a 128-bit SIMD unit;
# on a 128x128 MXU the equivalent crossover sits ~4x higher (napkin math in
# DESIGN.md; revisited empirically via repro.tune).
TPU_SCALE = 4.0

#: Op kinds the router understands, with their ``dims`` convention:
#:   gemm          (M, N, K)            2-D BLAS entry
#:   matmul        (*lead, K, N)        x.shape + (N,); M = prod(lead)
#:   batched_gemm  (G, C, K, N)         per-group problem is (C, K, N)
#:   ragged_gemm   (G, bm, K, N)        per-tile problem is (bm, K, N)
OPS = ("gemm", "matmul", "batched_gemm", "ragged_gemm")
_GROUPED = ("batched_gemm", "ragged_gemm")


@dataclasses.dataclass(frozen=True)
class Policy:
    """The single routing policy every GEMM-shaped op consults.

    ``backend`` picks the routing mode (how use-pallas is decided);
    ``kernels`` picks the non-GEMM kernel family (flash attention, SSD
    scan) — empty string derives it from ``backend``; ``iaat=False``
    short-circuits framework matmuls straight to ``jnp.matmul`` (a mode
    that stays XLA-compilable end to end).
    """
    backend: str = "auto"          # pallas | xla | auto | tuned
    # pallas interpret mode; None = decided by the platform (compiled on
    # a TPU backend, interpreted elsewhere — repro.runtime)
    interpret: Optional[bool] = None
    method: str = "dp"             # tiler: dp (ours) | greedy (paper)
    paper_thresholds: bool = False  # use the ARMv8 80/32 bounds verbatim
    max_plan_regions: int = 64     # plans with more regions route to XLA
    iaat: bool = True              # False: model matmuls bypass the router
    kernels: str = ""              # "pallas"|"xla"; "" = derive from backend

    def threshold(self, trans: str) -> float:
        base = (paper_table.PAPER_SMALL_THRESHOLD_TN if trans == "TN"
                else paper_table.PAPER_SMALL_THRESHOLD)
        return base if self.paper_thresholds else base * TPU_SCALE

    @property
    def kind(self) -> str:
        """Non-GEMM kernel family (the old ``Backend.kind``).  Derived
        when not pinned: every IAAT-capable backend implies the pallas
        family; only a forced-XLA policy drops to the reference paths."""
        return self.kernels or ("xla" if self.backend == "xla"
                                else "pallas")

    @property
    def pallas(self) -> bool:
        """True when attention/SSD use the Pallas kernels."""
        return self.kind == "pallas"

    def replace(self, **kw) -> "Policy":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class Decision:
    """How one op was routed — inspectable, so tests and the tune report
    can prove whether a profile (vs the analytical model) decided."""
    use_pallas: bool
    source: str                    # "forced" | "profile" | "analytical"
    op: str = "gemm"
    sig: Optional["kernelgen.KernelSig"] = None   # tuned 2-D plan override
    blocks: Optional[Tuple[int, int, int]] = None  # grouped (bm, bn, bk)


# --------------------------------------------------------------------------
# The ambient policy: one contextvar + a process default, installed once.
# --------------------------------------------------------------------------

_DEFAULT = Policy()
_POLICY: contextvars.ContextVar[Optional[Policy]] = \
    contextvars.ContextVar("repro_policy", default=None)


def current_policy() -> Policy:
    """The policy in effect: scoped override > installed default."""
    return _POLICY.get() or _DEFAULT


def install(policy: Optional[Policy] = None, **kw) -> Policy:
    """Set the process-wide default policy (model/launcher entry; call
    once — per-call overrides and :func:`using` scopes layer on top)."""
    global _DEFAULT
    _DEFAULT = (policy or _DEFAULT).replace(**kw) if kw else \
        (policy or _DEFAULT)
    return _DEFAULT


@contextlib.contextmanager
def using(policy: Optional[Policy] = None, **kw):
    """Scoped policy override (replaces the old per-call
    ``dispatch.configure`` churn for tests/benchmarks)."""
    base = policy or current_policy()
    tok = _POLICY.set(base.replace(**kw) if kw else base)
    try:
        yield current_policy()
    finally:
        _POLICY.reset(tok)


def _resolve(policy: Optional[Policy]) -> Policy:
    return policy if policy is not None else current_policy()


#: CLI / launcher backend names -> Policy (one place, so every entry
#: point — train, serve, examples — accepts the same set).
POLICY_NAMES = ("xla", "pallas", "auto", "tuned")


def named_policy(name: str, *, interpret: Optional[bool] = None) -> Policy:
    """Build the Policy a launcher flag means.

    ``xla``    — forced XLA everywhere.
    ``pallas`` — pallas kernels with input-aware GEMM routing (the old
                 ``Backend("pallas", iaat=True)``).
    ``auto``   — same routing, kernel family derived.
    ``tuned``  — route by the measured DeviceProfile (repro.tune).

    ``interpret=None`` leaves interpret mode to the platform
    (:func:`repro.runtime.pallas_interpret`).
    """
    if name == "xla":
        return Policy(backend="xla", kernels="xla", iaat=False,
                      interpret=interpret)
    if name == "pallas":
        return Policy(backend="auto", kernels="pallas", iaat=True,
                      interpret=interpret)
    if name in ("auto", "tuned"):
        return Policy(backend=name, iaat=True, interpret=interpret)
    raise ValueError(f"unknown policy name {name!r}; "
                     f"expected one of {POLICY_NAMES}")


def small_enough(M: int, N: int, K: int, trans: str = "NN",
                 policy: Optional[Policy] = None) -> bool:
    """The paper's input-aware criterion: cbrt(MNK) <= threshold."""
    pol = _resolve(policy)
    return (M * N * K) ** (1.0 / 3.0) <= pol.threshold(trans)


# --------------------------------------------------------------------------
# The router.
# --------------------------------------------------------------------------

def _letter_of(dtype) -> str:
    if isinstance(dtype, str):
        return dtype
    return kernelgen.blas_letter(dtype)


def _grouped_problem(op: str, dims) -> Tuple[int, int, int, int]:
    if len(dims) != 4:
        raise ValueError(f"{op} dims must be (G, C|bm, K, N), got {dims}")
    G, C, K, N = (int(d) for d in dims)
    return G, C, K, N


class Router:
    """Routes every GEMM-shaped op through one decision path.

    A Router optionally pins a policy (else it reads the ambient one per
    call); ``route`` is pure w.r.t. its arguments + the active
    DeviceProfile, so traced callers can consult it at trace time.
    """

    def __init__(self, policy: Optional[Policy] = None):
        self._policy = policy

    @property
    def policy(self) -> Policy:
        return _resolve(self._policy)

    # -- decisions ---------------------------------------------------------

    def route(self, op: str, dims, dtype, trans: str = "NN") -> Decision:
        """Route one problem: forced backends first, then the measured
        DeviceProfile (``tuned`` mode), then the analytical criterion.

        Fallback order (DESIGN.md §Tuning): a ``tuned`` backend with no
        profile on disk, or with no entry for this size class, degrades
        to exactly the ``auto`` analytical decision — tuning can only
        ever refine the dispatch, never strand it.

        With observability on (the default), every call lands in the
        ``obs.ROUTES`` shape log — the observed input distribution that
        seeds traffic-aware tuning.  The log entry doubles as a decision
        memo: a decision is pure in (op, dims, dtype, trans), the
        resolved Policy *object* (held by identity — frozen, so identity
        implies equal fields) and the active-DeviceProfile generation,
        so a repeat shape is one dict hit instead of a recompute.
        ``REPRO_OBS=0`` bypasses all of it with one attribute check."""
        if op not in OPS:
            raise ValueError(f"unknown op {op!r}; expected one of {OPS}")
        pol = self.policy
        rl = obs.ROUTES
        if rl.on:
            letter = dtype if type(dtype) is str else \
                kernelgen.blas_letter(dtype)
            key = (op, letter, trans, tuple(dims), id(pol))
            h = rl.hits.get(key)
            if h is not None and h[1] is pol and h[2] == rl.gen:
                h[0] += 1
                return h[3]
            d = self._decide(op, dims, letter, trans, pol)
            rl.note(key, pol, d)
            # memo-miss only: the flight recorder sees every NEW shape
            # (and every recompute after a profile swap) while the hot
            # repeat-shape path above stays one dict probe
            obs.TRACE.emit("ROUTE_MISS",
                           arg=(op, letter, trans, list(key[3]), d.source))
            return d
        return self._decide(op, dims, _letter_of(dtype), trans, pol)

    def _decide(self, op: str, dims, letter: str, trans: str,
                pol: Policy) -> Decision:
        """The actual decision procedure (memoized via ``route``)."""
        if op in _GROUPED:
            return self._route_grouped(op, dims, letter, pol)
        if op == "matmul":
            if len(dims) < 2:
                raise ValueError(f"matmul dims must be (*lead, K, N), "
                                 f"got {dims}")
            lead, K, N = dims[:-2], int(dims[-2]), int(dims[-1])
            M = 1
            for d in lead:
                M *= int(d)
            dims = (M, N, K)
        M, N, K = (int(d) for d in dims)
        if pol.backend == "xla":
            return Decision(False, "forced", op)
        if pol.backend == "pallas":
            d = Decision(True, "forced", op)
        else:
            d = Decision(small_enough(M, N, K, trans, pol), "analytical", op)
            if pol.backend == "tuned":
                entry = self._profile_entry(M, N, K, letter, trans)
                if entry is not None:
                    d = Decision(entry.prefer_pallas, "profile", op,
                                 sig=entry.sig if entry.prefer_pallas
                                 else None)
        return self._plan_valve(d, M, N, K, letter, trans, pol)

    @staticmethod
    def _plan_valve(d: Decision, M, N, K, letter, trans,
                    pol: Policy) -> Decision:
        """A Pallas decision whose kernel plan needs more than
        ``max_plan_regions`` launches goes to XLA — as a decision of its
        own (``source="plan_overflow"``, counted under
        ``route.plan_overflow``), so the shape log shows it."""
        if not d.use_pallas:
            return d
        p = plan_mod.build_plan(M, N, K, letter, trans, pol.method,
                                override=d.sig)
        if p.num_kernel_calls <= pol.max_plan_regions:
            return d
        obs.counter("route.plan_overflow").inc()
        return Decision(False, "plan_overflow", d.op)

    def _route_grouped(self, op: str, dims, letter: str,
                       pol: Policy) -> Decision:
        """Grouped ops: the per-group (C, K, N) problem is the routing
        unit; the block choice travels in ``Decision.blocks`` (always
        populated — kernel entries need blocks even under a forced
        backend).  Ragged keeps the caller's row block: group sizes are
        traced, so only (bn, bk) may come from the profile."""
        from repro.kernels import grouped_gemm as _gg
        G, C, K, N = _grouped_problem(op, dims)
        dtype = kernelgen.BLAS_DTYPES.get(
            letter, kernelgen.FRAMEWORK_DTYPES.get(letter))
        analytical = _gg.pick_blocks(C, K, N, dtype)
        if op == "ragged_gemm":
            analytical = (C,) + analytical[1:]
        if pol.backend == "pallas":
            return Decision(True, "forced", op, blocks=analytical)
        if pol.backend == "xla":
            return Decision(False, "forced", op, blocks=analytical)
        if pol.backend == "tuned":
            # grouped kernels consume operands as stored — trans is NN.
            # Prefer an entry measured ON the grouped kernel (the online
            # tuner's ``grouped:``-namespace sweep); fall back to the
            # 2-D timing of the per-group shape for older profiles.
            entry = self._grouped_profile_entry(C, N, K, letter)
            if entry is not None:
                blocks = analytical
                if entry.sig is not None:
                    blocks = (entry.sig.bm, entry.sig.bn, entry.sig.bk)
                    if op == "ragged_gemm":
                        blocks = (C, entry.sig.bn, entry.sig.bk)
                return Decision(entry.prefer_pallas, "profile", op,
                                sig=entry.sig, blocks=blocks)
        return Decision(small_enough(C, N, K, "NN", pol), "analytical", op,
                        blocks=analytical)

    @staticmethod
    def _profile_entry(M, N, K, letter, trans):
        from repro.tune import profile as profile_mod
        prof = profile_mod.active_profile()
        if prof is None:
            return None
        entry = prof.lookup_dims(M, N, K, letter, trans)
        if entry is None or not entry.measured:
            return None
        return entry

    @staticmethod
    def _grouped_profile_entry(C, N, K, letter):
        from repro.tune import profile as profile_mod
        prof = profile_mod.active_profile()
        if prof is None:
            return None
        entry = prof.lookup_grouped_dims(C, N, K, letter)
        if entry is None or not entry.measured:
            entry = prof.lookup_dims(C, N, K, letter, "NN")
        if entry is None or not entry.measured:
            return None
        return entry


_ROUTER = Router()


def route(op: str, dims, dtype, trans: str = "NN",
          policy: Optional[Policy] = None) -> Decision:
    """Module-level convenience over a shared :class:`Router`."""
    if policy is None:
        return _ROUTER.route(op, dims, dtype, trans)
    return Router(policy).route(op, dims, dtype, trans)


# --------------------------------------------------------------------------
# Executors: act on the Decision so callers never branch on backend.
# --------------------------------------------------------------------------

def _trans_str(trans_a: bool, trans_b: bool) -> str:
    return ("T" if trans_a else "N") + ("T" if trans_b else "N")


def _problem_dims(a_shape, b_shape, trans: str):
    M, Ka = (a_shape[1], a_shape[0]) if trans[0] == "T" else a_shape
    Kb, N = (b_shape[1], b_shape[0]) if trans[1] == "T" else b_shape
    if Ka != Kb:
        raise ValueError(f"K mismatch: {a_shape} {trans[0]} vs "
                         f"{b_shape} {trans[1]}")
    return M, N, Ka


def _xla_gemm(a, b, c, alpha, beta, trans: str):
    """XLA epilogue mirrors the Pallas ``epilogue_axpby`` template exactly:
    beta*c is accumulated in the accumulator dtype BEFORE the cast to
    result_type(a, b), so a ``c`` of any dtype cannot promote/demote the
    output relative to the kernel path."""
    opa = a.T if trans[0] == "T" else a
    opb = b.T if trans[1] == "T" else b
    out_dtype = jnp.result_type(a.dtype, b.dtype)
    acc = jnp.dot(opa, opb,
                  preferred_element_type=jnp.promote_types(
                      a.dtype, jnp.float32)
                  if not jnp.issubdtype(a.dtype, jnp.complexfloating)
                  else None)
    out = alpha * acc
    if c is not None:
        out = out + beta * c.astype(out.dtype)
    return out.astype(out_dtype)


def _plan_gemm(pol: Policy, d: Decision, a, b, c, alpha, beta, trans: str):
    M, N, K = _problem_dims(a.shape, b.shape, trans)
    letter = kernelgen.blas_letter(jnp.result_type(a.dtype, b.dtype))
    p = plan_mod.build_plan(M, N, K, letter, trans, pol.method,
                            override=d.sig)
    return plan_mod.execute(p, a, b, c, alpha, beta,
                            interpret=pol.interpret)


def gemm(a: jax.Array, b: jax.Array, c: Optional[jax.Array] = None,
         alpha=1.0, beta=0.0, trans_a: bool = False, trans_b: bool = False,
         *, policy: Optional[Policy] = None) -> jax.Array:
    """C = alpha * op(A) @ op(B) + beta * C with input-aware routing
    (the 2-D BLAS entry — the paper's ``iaat_gemm``)."""
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError("gemm is the 2-D BLAS entry; use matmul()")
    pol = _resolve(policy)
    trans = _trans_str(trans_a, trans_b)
    M, N, K = _problem_dims(a.shape, b.shape, trans)
    letter = kernelgen.blas_letter(jnp.result_type(a.dtype, b.dtype))
    d = route("gemm", (M, N, K), letter, trans, policy=pol)
    if not d.use_pallas:
        return _xla_gemm(a, b, c, alpha, beta, trans)
    return _plan_gemm(pol, d, a, b, c, alpha, beta, trans)


def matmul(x: jax.Array, w: jax.Array, *,
           policy: Optional[Policy] = None) -> jax.Array:
    """Framework matmul: (..., K) @ (K, N) with IAAT routing.

    Leading dims of ``x`` flatten into M (vmap-safe: shapes are concrete
    at trace time, and the flatten/unflatten is a pure reshape).  This is
    the hook through which every model projection reaches the paper's
    technique."""
    pol = _resolve(policy)
    if not pol.iaat:
        return jnp.matmul(x, w)
    letter = kernelgen.blas_letter(jnp.result_type(x.dtype, w.dtype))
    d = route("matmul", tuple(x.shape) + (w.shape[-1],), letter,
              policy=pol)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    if not d.use_pallas:
        # same epilogue as the declined gemm() path (f32-preferred
        # accumulation, one cast) so both entries agree numerically
        out = _xla_gemm(x2, w, None, 1.0, 0.0, "NN")
    else:
        out = _plan_gemm(pol, d, x2, w, None, 1.0, 0.0, "NN")
    return out.reshape(lead + (w.shape[-1],))


def batched_gemm(x: jax.Array, w: jax.Array, *,
                 policy: Optional[Policy] = None) -> jax.Array:
    """Equal-capacity grouped GEMM: x (G, C, K) @ w (G, K, N) -> (G, C, N),
    routed per the per-group problem; falls back to a batched einsum when
    the decision is XLA."""
    pol = _resolve(policy)
    G, C, K = x.shape
    N = w.shape[-1]
    d = route("batched_gemm", (G, C, K, N),
              jnp.result_type(x.dtype, w.dtype), policy=pol)
    if not d.use_pallas:
        return jnp.einsum("gck,gkn->gcn", x, w)
    from repro.kernels import grouped_gemm as _gg
    return _gg.batched_gemm(x, w, interpret=pol.interpret, blocks=d.blocks)


def ragged_gemm(x: jax.Array, w: jax.Array, tile_group_ids: jax.Array,
                *, bm: int = 128,
                policy: Optional[Policy] = None) -> jax.Array:
    """Ragged grouped GEMM (group-contiguous rows, traced group sizes):
    x (T, K) @ w (G, K, N) -> (T, N); XLA fallback gathers each tile's
    expert weight and einsums."""
    pol = _resolve(policy)
    T, K = x.shape
    G, _, N = w.shape
    d = route("ragged_gemm", (G, bm, K, N),
              jnp.result_type(x.dtype, w.dtype), policy=pol)
    if not d.use_pallas:
        wt = w[tile_group_ids]                    # (T//bm, K, N)
        xt = x.reshape(-1, bm, K)
        return jnp.einsum("tbk,tkn->tbn", xt, wt).reshape(T, N)
    from repro.kernels import grouped_gemm as _gg
    return _gg.ragged_gemm(x, w, tile_group_ids, bm=bm,
                           interpret=pol.interpret, blocks=d.blocks)
