"""Operations of one decode step of the ``dense`` family (GQA attention
and a SwiGLU MLP), from the configuration's shapes.  ``s`` is the
configuration namespace."""
from __future__ import annotations


def layer_gemms(s):
    """(K, N) of every weight GEMM of one layer, one entry per call."""
    d, hd = s.d_model, s.head_dim
    q, kv = s.n_heads * hd, s.n_kv_heads * hd
    return [(d, q), (d, kv), (d, kv), (q, d),
            (d, s.d_ff), (d, s.d_ff), (s.d_ff, d)]


def head_gemms(s):
    return [(s.d_model, s.vocab)]


def decode_flops(s, n_active: int, ctx_sum: int) -> float:
    """Model FLOPs of one decode step: the weight GEMMs of ``n_active``
    live slots, and attention over each slot's own context (``ctx_sum``
    keys in all, per layer): 2 * H * head_dim for the scores and as many
    for the weighted sum, per key."""
    per_token = 2 * sum(k * n for k, n in layer_gemms(s)) * s.n_layers
    per_token += 2 * sum(k * n for k, n in head_gemms(s))
    attn = 4 * s.n_heads * s.head_dim * s.n_layers * float(ctx_sum)
    return float(n_active) * per_token + attn
