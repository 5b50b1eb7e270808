"""Plain reference for the ``dense`` family: a decoder with grouped-query
attention and rotary positions (GLM-4 as the program runs it).

Per layer: RMSNorm; q, k, v projections; rotary positions on the whole
head, the first and second halves rotated as a pair with frequencies
theta^(-i/half); causal softmax attention, query head h reading key/value
head h // (H / Hkv), scaled by head_dim^-1/2; the output projection and
the residual; RMSNorm; SwiGLU, silu(x W_g) * (x W_u) W_d; the residual.
Then a final RMSNorm and the unembedding.  Float32 throughout, matmuls at
HIGHEST precision, attention in blocks of queries so it fits at 4096
positions.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import common as C

#: Queries per attention block.
QBLOCK = 256


def rules(s):
    d, L = s.d_model, s.n_layers
    scales = {
        "embed": d ** -0.5, "unembed": d ** -0.5,
        "wq": d ** -0.5, "wk": d ** -0.5, "wv": d ** -0.5,
        "wo": (s.n_heads * s.head_dim) ** -0.5 / math.sqrt(2.0 * L),
        "wg": d ** -0.5, "wu": d ** -0.5,
        "wd": s.d_ff ** -0.5 / math.sqrt(2.0 * L),
    }

    def rule(path, shape, key):
        name = path[-1]
        if name in ("ln1", "ln2", "final_norm"):
            return C.norm_weight(key, shape)
        if name in scales:
            return C.normal(key, shape, scales[name])
        raise KeyError(f"no initialisation rule for parameter {path}")
    return rule


def unembedding(params):
    return params["unembed"]


def _rope(x, pos, theta):
    """x (T, H, hd); pos (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _one(params, s, tokens, dot):
    """tokens (T,) -> (T, d)."""
    T = tokens.shape[0]
    H, Hkv, hd = s.n_heads, s.n_kv_heads, s.head_dim
    rep = H // Hkv
    pos = jnp.arange(T)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, blk):
        a, f = blk["attn"], blk["mlp"]
        h = C.rmsnorm(x, blk["ln1"], s.norm_eps)
        q = _rope(dot(h, a["wq"]).reshape(T, H, hd), pos, s.rope_theta)
        k = _rope(dot(h, a["wk"]).reshape(T, Hkv, hd), pos, s.rope_theta)
        v = dot(h, a["wv"]).reshape(T, Hkv, hd)
        qg = q.reshape(T // QBLOCK, QBLOCK, Hkv, rep, hd)

        def block(args):
            qb, q0 = args
            sc = jnp.einsum("qgrd,kgd->grqk", qb, k,
                            precision=C.HIGHEST) * hd ** -0.5
            ok = pos[None, :] <= (q0 + jnp.arange(QBLOCK))[:, None]
            p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
            return jnp.einsum("grqk,kgd->qgrd", p, v, precision=C.HIGHEST)

        o = lax.map(block, (qg, jnp.arange(T // QBLOCK) * QBLOCK))
        x = x + dot(o.reshape(T, H * hd), a["wo"])
        h2 = C.rmsnorm(x, blk["ln2"], s.norm_eps)
        x = x + dot(jax.nn.silu(dot(h2, f["wg"])) * dot(h2, f["wu"]),
                    f["wd"])
        return x, None

    x, _ = lax.scan(layer, x, params["blocks"])
    return C.rmsnorm(x, params["final_norm"], s.norm_eps)


def hidden(params, s, tokens, dot):
    """tokens (B, T) -> final-normed hidden states (B, T, d), one request
    at a time."""
    if tokens.shape[1] % QBLOCK:
        raise ValueError(f"length {tokens.shape[1]} is not a multiple of "
                         f"{QBLOCK}")
    return lax.map(lambda t: _one(params, s, t, dot), tokens)
