"""Plain reference for the ``hybrid`` family: Zamba2 (Zyphra; the
published config of Zamba2-7B-Instruct and the layer equations of the
public Zamba2 modelling code).

The embedding e = E[tokens] starts the residual stream, x = e.  Layer i
is the j-th hybrid layer when it is the j-th of ``hybrid_layers`` below
``n_layers``; it then runs shared block b = j % n_shared_blocks first:

    u = rmsnorm(concat(x, e)) * w1_b                   (width 2 d_model)
    a = attn_b(u)      q, k, v = u W_q, u W_k, u W_v; RoPE on the whole
                       head; causal softmax of q k^T (head_dim / 2)^-1/2,
                       query head h reading key/value head h // (H / Hkv);
                       then W_o (width d_model)
    h = rmsnorm(a) * w2_b
    g = h W_g + (h A_j) Bg_j,   v = h W_u + (h A_j) Bu_j
    t = ((gelu(g) * v) W_d) L_j                        (exact GELU)

and its mixer reads x + t; every other layer's mixer reads x.  The block
has no residual of its own, and A_j, Bg_j, Bu_j and L_j belong to the
application.  The layer is then x + mamba(rmsnorm(mixer input) * w_ln),
with Mamba-2 as in ``ssm.py`` but B and C in ``n_groups`` groups (head h
reads group h // (nh / n_groups)) and the gated RMSNorm taken over each
group's d_inner / n_groups channels.  Then a final RMSNorm and the tied
unembedding.  Float32 throughout, matmuls at HIGHEST precision, the
recurrence token by token, attention in blocks of queries so it fits at
4096 positions.
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
    """Initialisation of each parameter leaf, by name."""
    d, L, ss = s.d_model, s.n_layers, s.ssm
    di = ss["expand"] * d
    scales = {
        "embed": d ** -0.5, "in_proj": d ** -0.5,
        "conv_w": 0.2, "conv_b": 0.05,
        "out_proj": di ** -0.5 / math.sqrt(2.0 * L),
        "wq": (2 * d) ** -0.5, "wk": (2 * d) ** -0.5, "wv": (2 * d) ** -0.5,
        "wo": (s.n_heads * s.head_dim) ** -0.5 / math.sqrt(2.0 * L),
        "wg": d ** -0.5, "wu": d ** -0.5,
        "wd": s.d_ff ** -0.5 / math.sqrt(2.0 * L),
        "lora_a": d ** -0.5, "lora_bg": s.adapter_rank ** -0.5,
        "lora_bu": s.adapter_rank ** -0.5, "linear": d ** -0.5,
    }

    def rule(path, shape, key):
        name = path[-1]
        if name in ("ln1", "ln2", "final_norm", "norm_w"):
            return C.norm_weight(key, shape)
        if name in scales:
            return C.normal(key, shape, scales[name])
        if name == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name == "D":
            return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
        if name == "dt_bias":
            lo, hi = math.log(ss["dt_min"]), math.log(ss["dt_max"])
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
            return jnp.log(jnp.expm1(dt))          # softplus^-1(dt)
        raise KeyError(f"no initialisation rule for parameter {path}")
    return rule


def unembedding(params):
    return params["embed"].T


def _rope(x, pos, theta):
    """x (T, H, hd); pos (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rmsnorm_groups(x, w, eps, groups):
    shape = x.shape
    x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x.reshape(shape) * w.astype(jnp.float32)


def _shared(params, s, b, j, x, e, pos, dot):
    """t of application j (shared block b) for one request: (T, d)."""
    T = x.shape[0]
    H, Hkv, hd = s.n_heads, s.n_kv_heads, s.head_dim
    rep = H // Hkv
    p = params["shared"][b]
    ap = jax.tree.map(lambda w: w[j], params["apps"])
    a = p["attn"]
    u = C.rmsnorm(jnp.concatenate([x, e], axis=-1), p["ln1"], s.norm_eps)
    q = _rope(dot(u, a["wq"]).reshape(T, H, hd), pos, s.rope_theta)
    k = _rope(dot(u, a["wk"]).reshape(T, Hkv, hd), pos, s.rope_theta)
    v = dot(u, a["wv"]).reshape(T, Hkv, hd)
    qb = min(QBLOCK, T)
    qg = q.reshape(T // qb, qb, Hkv, rep, hd)

    def block(args):
        qblk, q0 = args
        sc = jnp.einsum("qgrd,kgd->grqk", qblk, k,
                        precision=C.HIGHEST) * (hd / 2) ** -0.5
        ok = pos[None, :] <= (q0 + jnp.arange(qb))[:, None]
        pr = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", pr, v, precision=C.HIGHEST)

    o = lax.map(block, (qg, jnp.arange(T // qb) * qb))
    h = C.rmsnorm(dot(o.reshape(T, H * hd), a["wo"]), p["ln2"], s.norm_eps)
    f = p["mlp"]
    lo = dot(h, ap["lora_a"])
    g = dot(h, f["wg"]) + dot(lo, ap["lora_bg"])
    up = dot(h, f["wu"]) + dot(lo, ap["lora_bu"])
    y = dot(jax.nn.gelu(g, approximate=False) * up, f["wd"])
    return dot(y, ap["linear"])


def _mamba(m, s, x, dot):
    """One Mamba-2 mixer over one request: x (T, d) -> (T, d)."""
    ss = s.ssm
    N, P, K, G = ss["d_state"], ss["head_dim"], ss["d_conv"], ss["n_groups"]
    di = ss["expand"] * s.d_model
    nh, GN = di // P, G * N
    T = x.shape[0]
    proj = dot(x, m["in_proj"])
    z, xs, Bm, Cm, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + GN, 2 * di + 2 * GN], axis=-1)
    u = jnp.concatenate([xs, Bm, Cm], axis=-1)
    up = jnp.pad(u, ((K - 1, 0), (0, 0)))
    w = m["conv_w"].astype(jnp.float32)
    conv = sum(up[i:i + T] * w[i] for i in range(K))
    u = jax.nn.silu(conv + m["conv_b"].astype(jnp.float32))
    xs = u[:, :di].reshape(T, nh, P)
    group = jnp.arange(nh) // (nh // G)                # head -> its group
    Bc = u[:, di:di + GN].reshape(T, G, N)[:, group]   # (T, nh, N)
    Cc = u[:, di + GN:].reshape(T, G, N)[:, group]
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(m["A_log"].astype(jnp.float32))

    def step(hs, inp):
        x_t, dt_t, b_t, c_t = inp
        hs = (hs * jnp.exp(dt_t * A)[:, None, None]
              + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return hs, jnp.einsum("hpn,hn->hp", hs, c_t, precision=C.HIGHEST)

    _, y = lax.scan(step, jnp.zeros((nh, P, N), jnp.float32),
                    (xs, dt, Bc, Cc))
    y = y + m["D"].astype(jnp.float32)[:, None] * xs
    y = _rmsnorm_groups(y.reshape(T, di) * jax.nn.silu(z), m["norm_w"],
                        s.norm_eps, G)
    return dot(y, m["out_proj"])


def _one(params, s, tokens, dot):
    """tokens (T,) -> final-normed hidden states (T, d)."""
    pos = jnp.arange(tokens.shape[0])
    e = params["embed"][tokens].astype(jnp.float32)
    x = e
    apps = [i for i in s.hybrid_layers if i < s.n_layers]
    for i in range(s.n_layers):
        blk = jax.tree.map(lambda w: w[i], params["blocks"])
        xin = x
        if i in apps:
            j = apps.index(i)
            xin = x + _shared(params, s, j % s.n_shared_blocks, j, x, e, pos,
                              dot)
        h = C.rmsnorm(xin, blk["ln1"], s.norm_eps)
        x = x + _mamba(blk["mixer"], s, h, dot)
    return C.rmsnorm(x, params["final_norm"], s.norm_eps)


def hidden(params, s, tokens, dot):
    """tokens (B, T) -> final-normed hidden states (B, T, d), one request
    at a time."""
    qb = min(QBLOCK, tokens.shape[1])
    if tokens.shape[1] % qb:
        raise ValueError(f"length {tokens.shape[1]} is not a multiple of "
                         f"{qb}")
    return lax.map(lambda t: _one(params, s, t, dot), tokens)
