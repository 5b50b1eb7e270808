"""What the plain references share: the matmul in two precisions, RMSNorm,
and seeded weights in the program's parameter layout.

The references import nothing of the program.  They read the model's
sizes from a plain namespace (``shape``) built from the configuration
file, and they make their own weights from the seed; the harness hands
the program the same weights, made by the same function before the
window, so the program takes nothing the reference made and the
reference takes nothing the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: Largest finite float8_e4m3fn.
E4M3_MAX = 448.0


def dot_f32(x, w):
    """The reference: float32 operands, float32 accumulation, every pass."""
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def _fp8(v, axis):
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (v / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def dot_fp8(x, w):
    """The control: both operands rounded to float8_e4m3fn, activations
    scaled per row and weights per output column (the usual fp8 GEMM
    recipe), products accumulated in float32."""
    x = _fp8(x.astype(jnp.float32), -1)
    w = _fp8(w.astype(jnp.float32), -2)
    return jnp.matmul(x, w, precision=HIGHEST)


DOTS = {"f32": dot_f32, "fp8": dot_fp8}


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def make_params(rules, template, key):
    """Fill every leaf of ``template`` (the program's parameter pytree of
    shapes and dtypes) by ``rules(path, shape, key) -> float32 array``,
    one key per leaf in the tree's own order.  Call under ``jax.jit``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        names = tuple(getattr(p, "key", getattr(p, "name", p))
                      for p in path)
        out.append(rules(names, leaf.shape, k).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def norm_weight(key, shape):
    """Norm gains near 1, not exactly 1, so the comparison sees them."""
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
