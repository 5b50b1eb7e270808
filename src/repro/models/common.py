"""Shared model machinery: the IAAT matmul hook, norms, RoPE, init/spec
utilities.

The ``be`` threaded through the model stack is a
:class:`repro.api.Policy` — the one frozen routing config — so the
layers consult the router directly; ``mm`` never re-enters a contextvar
per projection.  (The old two-axis ``Backend`` selector is gone; use
``api.Policy`` / ``api.named_policy``.)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import api
from repro.api import Policy

Params = Dict[str, Any]
Specs = Dict[str, Any]

#: Canonical policies for the two reference operating points: plain
#: XLA everywhere, and pallas kernels with input-aware
#: GEMM routing (interpreted on CPU, compiled on a TPU).
XLA = api.named_policy("xla")
PALLAS = api.named_policy("pallas")


def mm(x: jax.Array, w: jax.Array,
       be: Optional[Policy] = None) -> jax.Array:
    """The framework matmul: every projection goes through here, so the
    paper's input-aware dispatch applies uniformly.  ``be`` defaults to
    the ambient installed policy (``api.install``/``api.using``)."""
    return api.matmul(x, w.astype(x.dtype), policy=be)


def rmsnorm(x: jax.Array, w: Optional[jax.Array], eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, S, D); positions: (B, S) or (S,)."""
    D = x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, None, :, None].astype(jnp.float32) * freq  # (B,1,S,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# init / spec utilities.
# --------------------------------------------------------------------------

def ninit(key, shape, scale: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def stack_init(init_fn, key, n: int) -> Params:
    """vmap a per-layer init over ``n`` layers -> stacked ("layers", ...)"""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def stack_specs(specs: Specs) -> Specs:
    """Prepend the "layers" logical axis to every spec in the tree."""
    return jax.tree.map(lambda s: ("layers",) + tuple(s), specs,
                        is_leaf=lambda s: isinstance(s, tuple))


def assert_same_structure(params: Params, specs: Specs) -> None:
    pt = jax.tree.structure(params)
    st = jax.tree.structure(specs, is_leaf=lambda s: isinstance(s, tuple))
    if pt != st:
        raise ValueError(f"param/spec structure drift:\n{pt}\nvs\n{st}")


def count_params(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
