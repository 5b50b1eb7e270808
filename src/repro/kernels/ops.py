"""Jit'd public wrappers for every kernel in this package.

These are the callables examples/benchmarks/models import.  Shape/flag
arguments that select a kernel instance are static; array arguments are
traced.  GEMM-shaped entries route through :mod:`repro.api` (one Policy
+ Router for every shape), so the paper's technique — and any measured
DeviceProfile — applies uniformly; the grouped entries resolve their
block sizes through ``api.route`` when the caller does not pin them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import api
from repro.kernels import flash_attention as _fa
from repro.kernels import grouped_gemm as _gg
from repro.kernels import ssd as _ssd


def gemm(a, b, c=None, alpha=1.0, beta=0.0, trans_a=False, trans_b=False):
    """BLAS-style small-GEMM entry (input-aware dispatch)."""
    return api.gemm(a, b, c, alpha, beta, trans_a, trans_b)


def matmul(x, w):
    """Framework ND matmul (ambient policy)."""
    return api.matmul(x, w)


def _grouped_blocks(op, G, C, K, N, dtype, bm=None):
    dims = (G, bm if bm is not None else C, K, N)
    return api.route(op, dims, dtype).blocks


@functools.partial(jax.jit, static_argnames=("interpret", "blocks"))
def _batched_gemm_jit(x, w, *, interpret=None, blocks=None):
    return _gg.batched_gemm(x, w, interpret=interpret, blocks=blocks)


def batched_gemm(x, w, *, interpret=None, blocks=None):
    """Always-Pallas grouped kernel entry; ``blocks=None`` resolves the
    block sizes through the router (profile-refined under
    ``backend="tuned"``, the analytical table otherwise)."""
    if blocks is None:
        G, C, K = x.shape
        blocks = _grouped_blocks("batched_gemm", G, C, K, w.shape[-1],
                                 jnp.result_type(x.dtype, w.dtype))
    return _batched_gemm_jit(x, w, interpret=interpret, blocks=blocks)


@functools.partial(jax.jit, static_argnames=("bm", "interpret", "blocks"))
def _ragged_gemm_jit(x, w, tile_group_ids, *, bm=128, interpret=None,
                     blocks=None):
    return _gg.ragged_gemm(x, w, tile_group_ids, bm=bm,
                           interpret=interpret, blocks=blocks)


def ragged_gemm(x, w, tile_group_ids, *, bm=128, interpret=None,
                blocks=None):
    """Always-Pallas ragged kernel entry; block resolution as above (the
    row block ``bm`` stays caller-pinned — group sizes are traced)."""
    if blocks is None:
        T, K = x.shape
        G, _, N = w.shape
        blocks = _grouped_blocks("ragged_gemm", G, T, K, N,
                                 jnp.result_type(x.dtype, w.dtype), bm=bm)
    return _ragged_gemm_jit(x, w, tile_group_ids, bm=bm,
                            interpret=interpret, blocks=blocks)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "scale", "bq", "bkv", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    scale=None, bq=128, bkv=128, interpret=None):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, scale=scale, bq=bq,
                               bkv=bkv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=128, interpret=None):
    return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)
