"""Mamba-2 block (SSD) — attention-free sequence mixing.

Training runs the chunked SSD (Pallas kernel or jnp oracle).  Serving
runs :func:`paged_step`, one layer over a token chunk with an explicit
per-slot carry, and picks the recurrence by the chunk length: a prefill
chunk (C > 1) runs the chunked SSD form (``ref.ssd_chunk``, every dot at
HIGHEST precision, the state in float32) from the slot's state, and the
decode step (C == 1) runs the serial one-token step
(``ref.ref_ssd_decode_step``).  So the carry is bit-stable for a given
chunking, not across chunkings.  Recompute-resume after preemption stays
exact at temperature 0 because a replayed request is chunked as it was
first served: its prompt rows take the chunk numerics, and the rows that
decode generated before the preemption take the serial step.
The short causal conv is implemented as ``d_conv`` shifted adds
(compiles everywhere, no conv primitive needed).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.kernels import ref
from repro.api import Policy
from repro.models.common import mm, ninit, rmsnorm
from repro.parallel.ctx import constrain


def init_mamba(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    s = cfg.ssm
    d, di, N = cfg.d_model, cfg.d_inner, s.d_state
    nh = cfg.ssm_heads
    ch = di + 2 * N                       # conv channels: x, B, C streams
    ks = jax.random.split(key, 6)
    sc = 1.0 / math.sqrt(d)
    dt = jnp.exp(jax.random.uniform(ks[4], (nh,), jnp.float32)
                 * (math.log(s.dt_max) - math.log(s.dt_min))
                 + math.log(s.dt_min))
    return {
        "in_proj": ninit(ks[0], (d, 2 * di + 2 * N + nh), sc, dtype),
        "conv_w": ninit(ks[1], (s.d_conv, ch), 0.2, dtype),
        "conv_b": jnp.zeros((ch,), dtype),
        "A_log": jnp.log(jnp.abs(
            jax.random.uniform(ks[2], (nh,), jnp.float32) * 15 + 1)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(dt)),   # softplus^{-1}(dt)
        "norm_w": jnp.ones((di,), dtype),
        "out_proj": ninit(ks[3], (di, d),
                          1.0 / math.sqrt(di) / math.sqrt(2.0 * cfg.n_layers),
                          dtype),
    }


def mamba_specs(cfg: ModelConfig) -> Dict:
    return {
        "in_proj": ("embed", "inner"),
        "conv_w": (None, "inner"),
        "conv_b": ("inner",),
        "A_log": ("ssm_heads",),
        "D": ("ssm_heads",),
        "dt_bias": ("ssm_heads",),
        "norm_w": ("inner",),
        "out_proj": ("inner", "embed"),
    }


#: Mamba leaves the serving path reads in float32 (the conv window and
#: the SSM scalars, the gated norm's scale); ``in_proj`` and ``out_proj``
#: are consumed in the compute dtype (see ``lm.serving_params``).
MAMBA_F32_LEAVES = frozenset({"conv_w", "conv_b", "A_log", "D", "dt_bias",
                              "norm_w"})


def _causal_conv(x, w, b):
    """Depthwise causal conv via shifted adds. x: (B,S,ch); w: (K,ch)."""
    K = w.shape[0]
    out = x * w[-1][None, None, :]
    for i in range(1, K):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i or None][:, :x.shape[1]]
        out = out + shifted * w[K - 1 - i][None, None, :]
    return out + b[None, None, :]


def _conv_chunk(conv_state, x, w, b):
    """Causal conv over a chunk with explicit left context.

    conv_state: (B, K-1, ch) — the last K-1 inputs before this chunk;
    x: (B, C, ch).  Returns per-position outputs (B, C, ch) in the
    serving numerics (f32 window einsum + bias, cast back)."""
    K = w.shape[0]
    C = x.shape[1]
    full = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    win = jnp.stack([full[:, i:i + C] for i in range(K)], axis=2)
    y = jnp.einsum("btkc,kc->btc", win.astype(jnp.float32),
                   w.astype(jnp.float32)) + b.astype(jnp.float32)
    return y.astype(x.dtype)


def _project(p, x, cfg: ModelConfig, be: Policy):
    s = cfg.ssm
    di, N, nh = cfg.d_inner, s.d_state, cfg.ssm_heads
    proj = mm(x, p["in_proj"], be)
    z, xs, Bm, Cm, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    return z, xs, Bm, Cm, dt


def mamba(p: Dict, x, be: Policy, cfg: ModelConfig):
    """Whole-sequence (training) path, chunked SSD. x: (B, S, d) ->
    y (B, S, d).  Serving runs :func:`paged_step`."""
    s = cfg.ssm
    B, S, d = x.shape
    di, N, nh, P = cfg.d_inner, s.d_state, cfg.ssm_heads, s.head_dim
    z, xs, Bm, Cm, dt = _project(p, x, cfg, be)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    A = -jnp.exp(p["A_log"])

    conv_out = jax.nn.silu(_causal_conv(conv_in, p["conv_w"], p["conv_b"]))
    conv_out = constrain(conv_out, "batch", None, "inner")
    xs_c = constrain(conv_out[..., :di].reshape(B, S, nh, P),
                     "batch", None, "ssm_heads", None)
    B_c = conv_out[..., di:di + N].reshape(B, S, 1, N)
    C_c = conv_out[..., di + N:].reshape(B, S, 1, N)
    dt_c = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt_c = constrain(dt_c, "batch", None, "ssm_heads")
    if be.pallas:
        from repro.kernels import ops
        y = ops.ssd_scan(xs_c, dt_c, A, B_c, C_c, chunk=s.chunk,
                         interpret=be.interpret)
        y = y.astype(jnp.float32) + p["D"][None, None, :, None] \
            * xs_c.astype(jnp.float32)
    else:
        y = ref.ref_ssd(xs_c, dt_c, A, B_c, C_c, D_skip=p["D"],
                        chunk=s.chunk).astype(jnp.float32)
    y = y.reshape(B, S, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                p["norm_w"], cfg.norm_eps)
    return mm(y, p["out_proj"], be)


# --------------------------------------------------------------------------
# Serving recurrence (paged prefill chunks and slot decode).
# --------------------------------------------------------------------------

def init_paged_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    """Zero recurrent carry for ONE mamba layer and ``batch`` rows (one
    row per engine slot): (conv carry (batch, d_conv-1, ch), SSM state
    (batch, nh, P, N) in f32).  Fixed-size per row — slot-lifetime, not
    token-proportional."""
    s = cfg.ssm
    ch = cfg.d_inner + 2 * s.d_state
    conv = jnp.zeros((batch, s.d_conv - 1, ch), dtype)
    h = jnp.zeros((batch, cfg.ssm_heads, s.head_dim, s.d_state),
                  jnp.float32)
    return conv, h


def serve_ssd(h, xs, dt, A, Bm, Cm, valid, replay_from=None, *,
              chunk: int):
    """The SSM recurrence of one layer over C rows from the state ``h`` —
    the serving numerics.  h: (B, nh, P, N) f32; xs: (B, C, nh, P) and
    Bm/Cm: (B, C, N) in the compute dtype; dt: (B, C, nh) f32; valid
    (B, C): the rows that advance the state (the others get ``dt = 0``,
    so ``exp(dt*A) = 1`` and their input term vanishes).

    C == 1, the decode step: :func:`ref.ref_ssd_decode_step`.  C > 1, a
    prefill chunk: the chunked SSD form from ``h``, in sub-chunks of
    ``min(C, chunk)``, with every dot at HIGHEST precision — on the MXU
    a default-precision dot would round its float32 operands to
    bfloat16.  ``replay_from`` (B,) is the chunk-local row at which the
    rows that decode generated before a preemption start: the chunked
    form stops there, and only if some valid row lies at or past it do
    those rows run the serial step from the state it leaves (under
    ``lax.cond``, so a plain prompt chunk never enters the serial loop).
    Returns (h', y (B, C, nh, P) f32)."""
    B, C = xs.shape[:2]
    if C > 1:
        rows = jnp.arange(C)[None, :]
        if replay_from is None:
            replay_from = jnp.full((B,), C, jnp.int32)
        chunked = valid & (rows < replay_from[:, None])
        serial_rows = valid & ~chunked
        y, h = ref.ref_ssd(
            xs.astype(jnp.float32), jnp.where(chunked[..., None], dt, 0.0),
            A, Bm[:, :, None], Cm[:, :, None], chunk=min(C, chunk),
            return_state=True, h0=h, precision=lax.Precision.HIGHEST)
    else:
        serial_rows = valid
    dt_m = jnp.where(serial_rows[..., None], dt, 0.0)

    def step(hc, xs_t):
        xt, dtt, Bt, Ct = xs_t
        return ref.ref_ssd_decode_step(hc, xt, dtt, A, Bt, Ct)

    def serial(h):
        h, ys = lax.scan(step, h, (
            xs.transpose(1, 0, 2, 3).astype(jnp.float32),
            dt_m.transpose(1, 0, 2),
            Bm.transpose(1, 0, 2).astype(jnp.float32),
            Cm.transpose(1, 0, 2).astype(jnp.float32)))
        return h, ys.transpose(1, 0, 2, 3)                # (B, C, nh, P)

    if C == 1:
        return serial(h)

    def replay(h, y):
        h, ys = serial(h)
        return h, jnp.where(serial_rows[..., None, None], ys, y)

    return lax.cond(jnp.any(serial_rows), replay, lambda h, y: (h, y),
                    h, y)


def paged_step(p: Dict, x, be: Policy, cfg: ModelConfig, state: Tuple,
               *, seg_len=None, active=None, replay_from=None):
    """One mamba layer over a token chunk with an explicit carry — THE
    serving path.  x: (B, C, d); state = (conv_state (B, K-1, ch),
    h (B, nh, P, N)).

    ``seg_len`` (B,) marks how many of the C positions are real tokens
    (a prefill chunk's tail past the prompt is padding); ``active`` (B,)
    masks rows whose carry must not move (idle / mid-prefill slots
    sharing the decode batch).  Masked positions advance NEITHER the
    conv carry (the new carry is the last K-1 *valid* inputs) NOR the
    SSM state (dt is zeroed, so exp(dt*A) = 1 and the input term
    vanishes), and both are additionally re-selected through
    ``jnp.where`` so inactive rows are bitwise untouched.

    The recurrence is :func:`serve_ssd`: a prefill chunk (C > 1) runs
    the chunked SSD form, the decode step (C == 1) the serial step.
    ``replay_from`` (B,) is the chunk-local row from which a
    recompute-resume chunk replays tokens that decode generated before
    the preemption; those rows take the serial step, from the state the
    prompt rows leave, as they did in the request's first run — which
    keeps a preempted request's tokens bit-identical (the property the
    recompute-resume parity tests pin down).  Returns (y (B, C, d),
    (conv_state', h'))."""
    s = cfg.ssm
    B, C, _ = x.shape
    di, N, nh, P = cfg.d_inner, s.d_state, cfg.ssm_heads, s.head_dim
    conv_state, h = state
    if seg_len is None:
        seg_len = jnp.full((B,), C, jnp.int32)
    if active is None:
        active = jnp.ones((B,), bool)
    z, xs, Bm, Cm, dt = _project(p, x, cfg, be)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)          # (B, C, ch)
    A = -jnp.exp(p["A_log"])
    conv_out = jax.nn.silu(_conv_chunk(conv_state, conv_in,
                                       p["conv_w"], p["conv_b"]))
    xs_c = conv_out[..., :di].reshape(B, C, nh, P)
    B_c = conv_out[..., di:di + N]                            # (B, C, N)
    C_c = conv_out[..., di + N:]
    dt_c = jax.nn.softplus(dt.astype(jnp.float32)
                           + p["dt_bias"][None, None, :])     # (B, C, nh)
    valid = (jnp.arange(C)[None, :] < seg_len[:, None]) \
        & active[:, None]                                     # (B, C)
    h_new, y = serve_ssd(h, xs_c, dt_c, A, B_c, C_c, valid, replay_from,
                         chunk=s.chunk)
    y = y + p["D"][None, None, :, None] * xs_c.astype(jnp.float32)
    y = y.reshape(B, C, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                p["norm_w"], cfg.norm_eps)
    out = mm(y, p["out_proj"], be)
    # conv carry: rows [seg_len, seg_len + K-1) of [carry ; chunk] are
    # the last K-1 inputs at or before the segment end
    Kc = s.d_conv - 1
    full = jnp.concatenate([conv_state.astype(conv_in.dtype), conv_in],
                           axis=1)
    idx = seg_len[:, None] + jnp.arange(Kc)[None, :]          # (B, Kc)
    conv_new = jnp.take_along_axis(full, idx[..., None], axis=1)
    conv_new = jnp.where(active[:, None, None],
                         conv_new.astype(conv_state.dtype), conv_state)
    h_new = jnp.where(active[:, None, None, None], h_new, h)
    return out, (conv_new, h_new)
