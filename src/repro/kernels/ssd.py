"""Mamba-2 SSD (state-space duality) chunked scan kernel (Pallas TPU).

SSD computes attention-free sequence mixing as a cascade of *small GEMMs*
per chunk (C@Bᵀ (c x c), scores @ x (c x P), B'ᵀ @ x (N x P), C @ h
(c x P)) plus a tiny inter-chunk state recurrence — squarely IAAT's
small-GEMM regime, which is why this kernel lives in this framework: the
chunk size is an IAAT kernel-table choice (VMEM fit + MXU alignment), not
a hand-picked constant.

Layout: grid (B, H, n_chunks); the chunk axis is 'arbitrary' (sequential)
and the (P, N) state is carried across grid steps in a VMEM scratch —
Pallas guarantees scratch persistence along the trailing grid axis.
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


def _body(chunk: int, S: int, nc: int,
          x_ref, dt_ref, da_ref, b_ref, c_ref, o_ref, h_ref):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)        # (c, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)      # (c,)  [lane-padded view]
    da = da_ref[0, :, 0].astype(jnp.float32)      # (c,)
    Bm = b_ref[0, :, 0].astype(jnp.float32)       # (c, N)
    Cm = c_ref[0, :, 0].astype(jnp.float32)       # (c, N)

    # sequence-tail mask (last chunk may overhang S)
    tpos = ci * chunk + jnp.arange(chunk)
    valid = tpos < S
    dt = jnp.where(valid, dt, 0.0)
    da = jnp.where(valid, da, 0.0)

    cum = jnp.cumsum(da)                           # (c,) inclusive
    seg_total = cum[-1]

    # intra-chunk: L[t,s] = exp(cum_t - cum_s) for s <= t
    diff = cum[:, None] - cum[None, :]
    tri = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, jnp.exp(diff), 0.0)
    cb = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)  # (c, c)
    scores = cb * L * dt[None, :]
    y = jnp.dot(scores, x, preferred_element_type=jnp.float32)  # (c, P)

    # inter-chunk: y += exp(cum_t) * C_t . h_prev
    h_prev = h_ref[...]                            # (N, P)
    y = y + jnp.exp(cum)[:, None] * jnp.dot(
        Cm, h_prev, preferred_element_type=jnp.float32)

    # state update: h = exp(total) h_prev + Σ_s exp(total - cum_s) dt_s B_s x_sᵀ
    w = (dt * jnp.exp(seg_total - cum))[:, None] * Bm   # (c, N)
    h_ref[...] = jnp.exp(seg_total) * h_prev + jnp.dot(
        w.T, x, preferred_element_type=jnp.float32)

    o_ref[0, :, 0] = y.astype(o_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int = 128,
             interpret: Optional[bool] = None) -> jax.Array:
    """x: (Bt, S, H, P); dt: (Bt, S, H); A: (H,); B, C: (Bt, S, G, N), head
    h reading group h // (H / G).

    Returns y: (Bt, S, H, P).  D-skip is applied by the caller."""
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    hpg = H // B.shape[2]                          # heads per B/C group
    nc = -(S // -chunk)
    Sp = nc * chunk
    if Sp != S:
        x = jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, Sp - S), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    dA = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
    # each head reads its group's B/C through the index maps
    body = functools.partial(_body, chunk, S, nc)
    out = pl.pallas_call(
        body,
        grid=(Bt, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda b, h, c: (b, c, h // hpg, 0)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda b, h, c: (b, c, h // hpg, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((Bt, Sp, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=runtime.pallas_interpret(interpret),
    )(x, dt, dA, B, C)
    return out[:, :S]
