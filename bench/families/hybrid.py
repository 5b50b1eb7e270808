"""Operations of one decode step of the ``hybrid`` family (Zamba2: Mamba-2
layers, and weight-shared attention blocks applied before the mixers of
the hybrid layers), from the configuration's shapes.  ``s`` is the
configuration namespace."""
from __future__ import annotations


def _sizes(s):
    ss = s.ssm
    di = ss["expand"] * s.d_model
    return di, ss["n_groups"] * ss["d_state"], di // ss["head_dim"], \
        ss["head_dim"], ss["d_state"]


def _applications(s) -> int:
    return sum(1 for i in s.hybrid_layers if i < s.n_layers)


def layer_gemms(s):
    """(K, N) of every weight GEMM of one mamba layer, one entry per
    call."""
    di, GN, nh, _P, _N = _sizes(s)
    return [(s.d_model, 2 * di + 2 * GN + nh), (di, s.d_model)]


def application_gemms(s):
    """(K, N) of every weight GEMM of one application of a shared block:
    q, k, v on concat(x, e), o, the gated MLP with its LoRA adapter, and
    the application's linear."""
    d, ff, r = s.d_model, s.d_ff, s.adapter_rank
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    return [(2 * d, q), (2 * d, kv), (2 * d, kv), (q, d),
            (d, r), (d, ff), (r, ff), (d, ff), (r, ff), (ff, d), (d, d)]


def head_gemms(s):
    """(K, N) of the GEMMs outside the layer stack (the tied unembedding
    over the real vocabulary)."""
    return [(s.d_model, s.vocab)]


def decode_flops(s, n_active: int, ctx_sum: int) -> float:
    """Model FLOPs of one decode step for ``n_active`` live slots: the
    weight GEMMs, the SSD state update and read-out (about six operations
    per state element), and each shared-block application's attention
    over each slot's own context (``ctx_sum`` keys in all): 2 * H *
    head_dim for the scores and as many for the weighted sum, per key."""
    di, _GN, nh, P, N = _sizes(s)
    apps = _applications(s)
    per_token = 2 * sum(k * n for k, n in layer_gemms(s)) * s.n_layers
    per_token += 6 * nh * P * N * s.n_layers
    per_token += 2 * sum(k * n for k, n in application_gemms(s)) * apps
    per_token += 2 * sum(k * n for k, n in head_gemms(s))
    attn = 4 * s.n_heads * s.head_dim * apps * float(ctx_sum)
    return float(n_active) * per_token + attn
