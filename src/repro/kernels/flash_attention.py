"""Tiled online-softmax attention (Pallas TPU), GQA + causal + sliding
window.

The IAAT connection: prefill attention at 32k+ is a cascade of
(bq x D) @ (D x bk) and (bq x bk) @ (bk x D) block GEMMs; the block sizes
are drawn from the same VMEM-allocator reasoning as the GEMM kernel table
(the flash working set q/k/v/acc/m/l must fit the budget with the
double-buffered pipeline).  Sliding-window blocks that cannot contribute
are skipped entirely (the boundary-processing-removal principle applied to
the attention mask).
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

NEG_INF = -1e30


def _cdiv(a: int, b: int) -> int:
    return -(a // -b)


def _body(bq: int, bkv: int, Sq: int, Sk: int, q_offset: int,
          causal: bool, window: Optional[int], scale: float, nk: int,
          q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = i * bq + q_offset
    k_start = j * bkv
    # block-level skip predicates (no work for fully-masked blocks)
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window is not None:
        live &= k_start + bkv - 1 > q_start - window

    @pl.when(live)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)
        # zero the Sk overhang of v: OOB-padded rows may be garbage/NaN and
        # 0-prob x NaN would poison the accumulator (cf. iaat_gemm K mask)
        krow = k_start + lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(krow < Sk, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi = q_start + lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        ki = k_start + lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        ok = ki < Sk
        if causal:
            ok &= ki <= qi
        if window is not None:
            ok &= ki > qi - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_ref[:, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, scale: Optional[float] = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D); returns (B, Hq, Sq, D).

    GQA via the kv BlockSpec index map (no repeat-materialisation of kv)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"GQA needs Hq % Hkv == 0, got {Hq}/{Hkv}")
    rep = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    nq, nk = _cdiv(Sq, bq), _cdiv(Sk, bkv)
    body = functools.partial(_body, bq, bkv, Sq, Sk, q_offset, causal,
                             window, scale, nk)
    return pl.pallas_call(
        body,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=runtime.pallas_interpret(interpret),
    )(q, k, v)
