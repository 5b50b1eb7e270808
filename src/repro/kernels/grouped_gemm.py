"""Grouped / batched small-GEMM kernels (Pallas TPU) — IAAT's ML habitat.

The paper motivates small GEMM with ML workloads; on TPU the dominant such
workload is MoE expert compute: G independent (tokens_g x K) @ (K x N)
products with small, *input-dependent* tokens_g.  Two kernels:

* ``batched_gemm``   — equal-capacity groups (the capacity-routed MoE
  layout): x (G, C, K) @ w (G, K, N).  Grid (G, gm, gn, gk); block sizes
  come from the IAAT kernel table for the (C, N, K) small-GEMM problem.
* ``ragged_gemm``    — group-contiguous rows with traced group sizes,
  group->tile mapping delivered through scalar prefetch (SMEM), the
  run-time-stage analogue for dropless MoE.  Rows must be padded per group
  to a multiple of the row-block (the dispatcher does this); padded rows
  are zero so they contribute nothing.

Block selection flows through ``repro.api`` (one Router for every GEMM
shape): a measured DeviceProfile entry for the per-group problem wins
under ``Policy(backend="tuned")``, and :func:`pick_blocks` below is the
analytical fallback the router uses for unmeasured classes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime
from repro.core import kernelgen, vmem
from repro.kernels.iaat_gemm import mask_k


def _cdiv(a: int, b: int) -> int:
    return -(a // -b)


def pick_blocks(C: int, K: int, N: int, dtype) -> tuple:
    """IAAT install-time table lookup for the per-group problem."""
    letter = kernelgen.blas_letter(dtype)
    table = kernelgen.kernel_table(letter, "NN")
    bm_c = [s.bm for s in table]
    bn_c = [s.bn for s in table]
    bk_c = [s.bk for s in table]
    bm = max([b for b in bm_c if b <= vmem.align_m(C, dtype)] or [min(bm_c)])
    bn = max([b for b in bn_c if b <= vmem.align_n(N, dtype)] or [min(bn_c)])
    bk = max([b for b in bk_c if b <= vmem.align_k(K, dtype)] or [min(bk_c)])
    while not vmem.fits_vmem(bm, bn, bk, dtype):
        bk = max(bk // 2, 128)
        if bk == 128 and not vmem.fits_vmem(bm, bn, bk, dtype):
            bn = max(bn // 2, 128)
            if bn == 128:
                bm = max(bm // 2, vmem.sublane(dtype))
                if bm == vmem.sublane(dtype):
                    break
    return bm, bn, bk


# --------------------------------------------------------------------------
# batched (equal-capacity) grouped GEMM
# --------------------------------------------------------------------------

def _batched_body(nk: int, K: int, bk: int, *refs):
    x_ref, w_ref, o_ref, acc_ref = refs
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]
    w = w_ref[0]
    if K % bk:
        x = mask_k(x, k, bk, K, 1)
        w = mask_k(w, k, bk, K, 0)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def batched_gemm(x: jax.Array, w: jax.Array, *,
                 interpret: Optional[bool] = None,
                 blocks: Optional[tuple] = None) -> jax.Array:
    """x: (G, C, K), w: (G, K, N) -> (G, C, N)."""
    G, C, K = x.shape
    _, _, N = w.shape
    bm, bn, bk = blocks or pick_blocks(C, K, N, x.dtype)
    gm, gn, nk = _cdiv(C, bm), _cdiv(N, bn), _cdiv(K, bk)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    return pl.pallas_call(
        functools.partial(_batched_body, nk, K, bk),
        grid=(G, gm, gn, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, C, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=runtime.pallas_interpret(interpret),
    )(x, w)


# --------------------------------------------------------------------------
# ragged grouped GEMM (scalar-prefetched group ids)
# --------------------------------------------------------------------------

def _ragged_body(nk: int, K: int, bk: int, gid_ref, *refs):
    x_ref, w_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[0]
    if K % bk:
        x = mask_k(x, k, bk, K, 1)
        w = mask_k(w, k, bk, K, 0)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def ragged_gemm(x: jax.Array, w: jax.Array, tile_group_ids: jax.Array,
                *, bm: int = 128, interpret: Optional[bool] = None,
                blocks: Optional[tuple] = None) -> jax.Array:
    """x: (T, K) group-contiguous (each group padded to bm rows, padding
    zeroed); w: (G, K, N); tile_group_ids: (T//bm,) int32 mapping each row
    tile to its expert.  Returns (T, N)."""
    T, K = x.shape
    G, _, N = w.shape
    if T % bm:
        raise ValueError(f"T={T} must be padded to bm={bm}")
    _, bn, bk = blocks or pick_blocks(bm, K, N, x.dtype)
    gm, gn, nk = T // bm, _cdiv(N, bn), _cdiv(K, bk)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(gm, gn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, gids: (i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k, gids: (gids[i], k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, gids: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_ragged_body, nk, K, bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, N), out_dtype),
        interpret=runtime.pallas_interpret(interpret),
    )(tile_group_ids.astype(jnp.int32), x, w)
