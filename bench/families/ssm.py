"""Operations of one decode step of the ``ssm`` family (Mamba-2), from
the configuration's shapes.  ``s`` is the configuration namespace."""
from __future__ import annotations


def _sizes(s):
    ss = s.ssm
    di = ss["expand"] * s.d_model
    return di, ss["d_state"], di // ss["head_dim"], ss["head_dim"]


def layer_gemms(s):
    """(K, N) of every weight GEMM of one layer, one entry per call."""
    di, N, nh, _P = _sizes(s)
    return [(s.d_model, 2 * di + 2 * N + nh), (di, s.d_model)]


def head_gemms(s):
    """(K, N) of the GEMMs outside the layer stack (the unembedding over
    the real vocabulary)."""
    return [(s.d_model, s.vocab)]


def decode_flops(s, n_active: int, ctx_sum: int) -> float:
    """Model FLOPs of one decode step for ``n_active`` live slots: the
    weight GEMMs and the state update and read-out of the SSD recurrence,
    about six operations per state element (decay, outer-product add,
    contraction with C).  The recurrence does not grow with context, so
    ``ctx_sum`` is unused."""
    di, N, nh, P = _sizes(s)
    per_token = 2 * sum(k * n for k, n in layer_gemms(s)) * s.n_layers
    per_token += 6 * nh * P * N * s.n_layers
    per_token += 2 * sum(k * n for k, n in head_gemms(s))
    return float(n_active) * per_token
