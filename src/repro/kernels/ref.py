"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each kernel in this package has a reference here, used by the per-kernel
allclose tests and — for attention/SSD — by the model's XLA path
(chunked formulations keep 32k+ sequences compilable without
materialising S x S score matrices).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


# --------------------------------------------------------------------------
# GEMM (paper oracle).
# --------------------------------------------------------------------------

def ref_gemm(a, b, c=None, alpha=1.0, beta=0.0, trans_a: bool = False,
             trans_b: bool = False):
    """C = alpha * op(A) @ op(B) + beta * C, computed by jnp."""
    opa = a.T if trans_a else a
    opb = b.T if trans_b else b
    if jnp.issubdtype(opa.dtype, jnp.complexfloating):
        out = jnp.asarray(alpha, opa.dtype) * (opa @ opb)
    else:
        acc = jnp.float64 if opa.dtype == jnp.float64 else jnp.float32
        out = (alpha * jnp.dot(opa, opb, preferred_element_type=acc))
        out = out.astype(jnp.result_type(a.dtype, b.dtype))
    if c is not None:
        out = out + jnp.asarray(beta, out.dtype) * c
    return out


def ref_grouped_gemm(x, w, group_sizes):
    """Per-group x[g_rows] @ w[g]: x (T, K), w (G, K, N), sizes (G,).

    Rows of x are laid out group-contiguously (sum(sizes) == T)."""
    G = w.shape[0]
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(group_sizes.astype(jnp.int32))[:-1]])
    T = x.shape[0]
    row = jnp.arange(T)[:, None]
    out = jnp.zeros((T, w.shape[-1]), jnp.result_type(x.dtype, w.dtype))
    for g in range(G):
        sel = (row >= starts[g]) & (row < starts[g] + group_sizes[g])
        xg = jnp.where(sel, x, 0)
        out = out + jnp.where(sel, xg @ w[g], 0)
    return out


# --------------------------------------------------------------------------
# Attention.
# --------------------------------------------------------------------------

def _mask_bias(sq: int, sk: int, q_offset: int, causal: bool,
               window: Optional[int], dtype):
    qi = jnp.arange(sq)[:, None] + q_offset
    ki = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok &= ki <= qi
    if window is not None:
        ok &= ki > qi - window
    return jnp.where(ok, 0.0, -jnp.inf).astype(dtype)


def ref_mha(q, k, v, *, causal: bool = True, window: Optional[int] = None,
            q_offset: int = 0, scale: Optional[float] = None):
    """Quadratic reference attention. q: (B, Hq, Sq, D), k/v: (B, Hkv, Sk, D).

    GQA: Hq must be a multiple of Hkv; kv heads are broadcast."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else D ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = logits + _mask_bias(Sq, k.shape[2], q_offset, causal, window,
                                 jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def chunked_mha(q, k, v, *, causal: bool = True,
                window: Optional[int] = None, q_offset: int = 0,
                scale: Optional[float] = None, kv_chunk: int = 1024):
    """Online-softmax attention scanning KV in chunks (flash-style, pure
    jnp + lax.scan).  This is both the oracle for the Pallas flash kernel
    at scale and the model's XLA attention path (memory O(S·c))."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    nc = -(Sk // -kv_chunk)
    pad = nc * kv_chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = k.reshape(B, Hkv, nc, kv_chunk, D).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, nc, kv_chunk, D).transpose(2, 0, 1, 3, 4)
    qf = q
    qi = jnp.arange(Sq)[:, None] + q_offset

    def step(carry, xs):
        # NB: the chunk counter ci lives in the CARRY, not in xs — a
        # loop-carried value cannot be hoisted, whereas an xs-derived mask
        # gets strength-reduced by XLA into a materialised
        # (nc, B, H, Sq, chunk) bool tensor (gigabytes at 32k).
        m, l, acc, ci = carry
        kb, vb = xs
        kb = jnp.repeat(kb, rep, axis=1)
        vb = jnp.repeat(vb, rep, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb,
                       preferred_element_type=jnp.float32) * scale
        ki = ci * kv_chunk + jnp.arange(kv_chunk)[None, :]
        ok = ki < Sk
        if causal:
            ok = ok & (ki <= qi)
        if window is not None:
            ok = ok & (ki > qi - window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(ok[None, None], p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc, ci + 1), None

    from repro.parallel.ctx import constrain
    m0 = constrain(jnp.full((B, Hq, Sq), -jnp.inf, jnp.float32),
                   "batch", "heads", None)
    l0 = constrain(jnp.zeros((B, Hq, Sq), jnp.float32),
                   "batch", "heads", None)
    a0 = constrain(jnp.zeros((B, Hq, Sq, D), jnp.float32),
                   "batch", "heads", None, None)
    # checkpoint the chunk step: without it, the backward pass saves the
    # (nc, B, H, Sq, chunk) f32 score stack — gigabytes at 32k context
    (m, l, acc, _), _ = lax.scan(
        jax.checkpoint(step), (m0, l0, a0, jnp.zeros((), jnp.int32)),
        (kc, vc))
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality).
# --------------------------------------------------------------------------

def ref_ssd_recurrent(x, dt, A, B, C, *, D_skip=None):
    """Ground-truth sequential recurrence (one step per token).

    x: (Bt, S, H, P); dt: (Bt, S, H); A: (H,) (negative);
    B, C: (Bt, S, G, N), head h reading group h // (H / G).
    h_t = exp(dt*A) h_{t-1} + dt * B_t x_t ;  y_t = C_t . h_t
    """
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = jnp.repeat(B.astype(jnp.float32), H // B.shape[2], axis=2)
    Cf = jnp.repeat(C.astype(jnp.float32), H // C.shape[2], axis=2)

    def step(h, t):
        # h: (Bt, H, P, N)
        da = jnp.exp(dtf[:, t] * A[None, :])            # (Bt, H)
        inp = (dtf[:, t, :, None, None] * xf[:, t, :, :, None]
               * Bf[:, t, :, None, :])                  # (Bt,H,P,N)
        h = h * da[..., None, None] + inp
        y = jnp.einsum("bhpn,bhn->bhp", h, Cf[:, t])
        return h, y

    h0 = jnp.zeros((Bt, H, P, N), jnp.float32)
    _, ys = lax.scan(step, h0, jnp.arange(S))
    y = ys.transpose(1, 0, 2, 3)                         # (Bt,S,H,P)
    if D_skip is not None:
        y = y + D_skip[None, None, :, None] * xf
    return y.astype(x.dtype)


def ssd_chunk(h, xc, dtc, A, Bc, Cc, *, precision=None):
    """One chunk of the chunked SSD from the state ``h`` before it:
    the intra-chunk 'attention-like' term plus the carried state's
    contribution, and the state after the chunk.

    h: (Bt,H,P,N) f32; xc: (Bt,c,H,P); dtc: (Bt,c,H); A: (H,) (negative);
    Bc/Cc: (Bt,c,N), shared by every head, or (Bt,c,H,N), one row per
    head.  A row with ``dtc = 0`` neither decays nor feeds the
    state, and adds nothing to any row's output (``L`` is lower
    triangular and the input weights carry ``dtc``), so masked rows pass
    the state through.  ``precision`` goes to every einsum.  Returns
    (h', y (Bt,c,H,P))."""
    c = xc.shape[1]
    tri = jnp.tril(jnp.ones((c, c), bool))
    dA = dtc * A[None, None, :]                         # (Bt,c,H)
    cum = jnp.cumsum(dA, axis=1)                        # inclusive
    tot = cum[:, -1]                                    # (Bt,H)
    decay = cum[:, :, None, :] - cum[:, None, :, :]     # (Bt,t,s,H)
    L = jnp.where(tri[None, :, :, None], jnp.exp(decay), 0.0)
    g = "h" if Bc.ndim == 4 else ""                     # per-head B/C
    cb = jnp.einsum(f"bt{g}n,bs{g}n->bts{g}", Cc, Bc, precision=precision)
    scores = (cb if g else cb[..., None]) * L * dtc[:, None]  # (Bt,t,s,H)
    y = jnp.einsum("btsh,bshp->bthp", scores, xc, precision=precision)
    y = y + jnp.einsum(f"bt{g}n,bhpn->bthp", Cc, h, precision=precision) \
        * jnp.exp(cum)[..., None]
    w = (dtc * jnp.exp(tot[:, None] - cum))[..., None] * xc  # (Bt,c,H,P)
    h = h * jnp.exp(tot)[..., None, None] \
        + jnp.einsum(f"bchp,bc{g}n->bhpn", w, Bc, precision=precision)
    return h, y


def _heads(m, H: int):
    """(..., G, N) groups as the heads read them: (..., N) for one group,
    shared by every head; else (..., H, N), head h reading group
    h // (H / G)."""
    G = m.shape[-2]
    return m[..., 0, :] if G == 1 else jnp.repeat(m, H // G, axis=-2)


def ref_ssd(x, dt, A, B, C, *, D_skip=None, chunk: int = 64,
            return_state: bool = False, h0=None, precision=None):
    """Chunked SSD (the paper-of-record algorithm, arXiv:2405.21060 §6):
    :func:`ssd_chunk` scanned over the chunks, the state carried between
    them.

    Mathematically identical to ``ref_ssd_recurrent``; O(S·c) memory.  This
    is the XLA model path; the Pallas kernel mirrors its block structure
    (each chunk is a cascade of small GEMMs — IAAT's habitat).  B, C:
    (Bt,S,G,N) in G groups, head h reading group h // (H / G).  ``h0``
    (Bt,H,P,N) is the state before the first token (zero by default);
    ``precision`` goes to every einsum of the chunks.
    """
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    nc = -(S // -chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = nc * chunk
    xf = x.astype(jnp.float32).reshape(Bt, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bt, nc, chunk, H)
    Bf = _heads(B.astype(jnp.float32).reshape(Bt, nc, chunk, -1, N), H)
    Cf = _heads(C.astype(jnp.float32).reshape(Bt, nc, chunk, -1, N), H)

    # one chunk per scan step: the vectorised form materialises a
    # (Bt, nc, c, c, H) decay tensor — O(S·c·H) memory, terabytes at
    # production shapes.  The scan keeps the working set at one chunk
    # (exactly the Pallas kernel's schedule) and the checkpointed step
    # keeps the backward pass from stacking the per-chunk scores.
    def step(h, inputs):
        xc, dtc, Bc, Cc = inputs      # (Bt,c,H,P) (Bt,c,H) (Bt,c,[H,]N) x2
        return ssd_chunk(h, xc, dtc, A, Bc, Cc, precision=precision)

    if h0 is None:
        from repro.parallel.ctx import constrain
        h0 = constrain(jnp.zeros((Bt, H, P, N), jnp.float32),
                       "batch", "ssm_heads", None, None)
    h_last, ys = lax.scan(
        jax.checkpoint(step), h0.astype(jnp.float32),
        (xf.transpose(1, 0, 2, 3, 4), dtf.transpose(1, 0, 2, 3),
         Bf.swapaxes(0, 1), Cf.swapaxes(0, 1)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bt, Sp, H, P)[:, :S]
    if D_skip is not None:
        y = y + D_skip[None, None, :, None] * x.astype(jnp.float32)[:, :S]
    y = y.astype(x.dtype)
    if return_state:
        return y, h_last
    return y


def ref_ssd_decode_step(h, x_t, dt_t, A, B_t, C_t):
    """One-token SSM recurrence for serving (state in, state out).

    h: (Bt,H,P,N); x_t: (Bt,H,P); dt_t: (Bt,H); B_t/C_t: (Bt,N), shared
    by every head, or (Bt,H,N), one row per head."""
    per_head = B_t.ndim == 3
    da = jnp.exp(dt_t * A[None, :])
    h = h * da[..., None, None] + (
        dt_t[..., None, None] * x_t[..., None]
        * (B_t[:, :, None, :] if per_head else B_t[:, None, None, :]))
    y = jnp.einsum("bhpn,bhn->bhp" if per_head else "bhpn,bn->bhp", h, C_t)
    return h, y


# --------------------------------------------------------------------------
# RMSNorm.
# --------------------------------------------------------------------------

def ref_rmsnorm(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)
