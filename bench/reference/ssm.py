"""Plain reference for the ``ssm`` family: Mamba-2 (SSD), arXiv:2405.21060.

Per layer: RMSNorm; one input projection to (z, x, B, C, dt); a depthwise
causal convolution of width ``d_conv`` over (x, B, C) and SiLU; the
selective state-space recurrence with one group

    h_t = exp(dt_t * A) h_t-1 + dt_t * x_t B_t^T,   y_t = h_t C_t + D x_t

with dt = softplus(dt + dt_bias) and A = -exp(A_log), run token by
token; a gated RMSNorm, rmsnorm(y * silu(z)) * w; the output projection;
the residual.  Then a final RMSNorm and the tied unembedding.  Float32
throughout, matmuls at HIGHEST precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import common as C


def rules(s):
    """Initialisation of each parameter leaf, by name."""
    di, L = s.ssm["expand"] * s.d_model, s.n_layers

    def rule(path, shape, key):
        name = path[-1]
        if name == "embed":
            return C.normal(key, shape, s.d_model ** -0.5)
        if name in ("ln1", "final_norm", "norm_w"):
            return C.norm_weight(key, shape)
        if name == "in_proj":
            return C.normal(key, shape, s.d_model ** -0.5)
        if name == "conv_w":
            return C.normal(key, shape, 0.2)
        if name == "conv_b":
            return C.normal(key, shape, 0.05)
        if name == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name == "D":
            return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
        if name == "dt_bias":
            lo, hi = math.log(s.ssm["dt_min"]), math.log(s.ssm["dt_max"])
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
            return jnp.log(jnp.expm1(dt))          # softplus^-1(dt)
        if name == "out_proj":
            return C.normal(key, shape, di ** -0.5 / math.sqrt(2.0 * L))
        raise KeyError(f"no initialisation rule for parameter {path}")
    return rule


def unembedding(params):
    return params["embed"].T


def hidden(params, s, tokens, dot):
    """tokens (B, T) -> final-normed hidden states (B, T, d)."""
    ss = s.ssm
    d, N, P, K = s.d_model, ss["d_state"], ss["head_dim"], ss["d_conv"]
    di = ss["expand"] * d
    nh = di // P
    Bsz, T = tokens.shape
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, blk):
        m = blk["mixer"]
        h = C.rmsnorm(x, blk["ln1"], s.norm_eps)
        proj = dot(h, m["in_proj"])
        z, xs, Bm, Cm, dt = jnp.split(
            proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
        u = jnp.concatenate([xs, Bm, Cm], axis=-1)
        up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        w = m["conv_w"].astype(jnp.float32)
        conv = sum(up[:, i:i + T] * w[i] for i in range(K))
        u = jax.nn.silu(conv + m["conv_b"].astype(jnp.float32))
        xs = u[..., :di].reshape(Bsz, T, nh, P)
        Bc, Cc = u[..., di:di + N], u[..., di + N:]
        dt = jax.nn.softplus(dt + m["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(m["A_log"].astype(jnp.float32))

        def step(hs, inp):
            x_t, dt_t, b_t, c_t = inp
            hs = (hs * jnp.exp(dt_t * A)[..., None, None]
                  + dt_t[..., None, None] * x_t[..., None]
                  * b_t[:, None, None, :])
            return hs, jnp.einsum("bhpn,bn->bhp", hs, c_t, precision=C.HIGHEST)

        h0 = jnp.zeros((Bsz, nh, P, N), jnp.float32)
        _, y = lax.scan(step, h0, (xs.swapaxes(0, 1), dt.swapaxes(0, 1),
                                   Bc.swapaxes(0, 1), Cc.swapaxes(0, 1)))
        y = y.swapaxes(0, 1) + m["D"].astype(jnp.float32)[:, None] * xs
        y = C.rmsnorm(y.reshape(Bsz, T, di) * jax.nn.silu(z), m["norm_w"],
                      s.norm_eps)
        return x + dot(y, m["out_proj"]), None

    x, _ = lax.scan(layer, x, params["blocks"])
    return C.rmsnorm(x, params["final_norm"], s.norm_eps)
