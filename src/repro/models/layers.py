"""Transformer layers: GQA attention (full / sliding-window / cross),
gated MLP, and capacity-routed MoE with sort-based dispatch.

Every projection goes through ``common.mm`` (the IAAT dispatch hook); the
full-sequence attention inner loop switches between the Pallas flash
kernel and the chunked-XLA oracle by the ``Policy``; serving attends over
a paged KV pool (:func:`paged_attend`); MoE expert compute switches
between ``ops.batched_gemm`` (Pallas, the paper's batched-small-GEMM
habitat) and a batched einsum (the XLA path).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.kernels import ref
from repro.api import Policy
from repro.models.common import mm, ninit, rmsnorm, rope
from repro.parallel.ctx import constrain


# --------------------------------------------------------------------------
# Attention.
# --------------------------------------------------------------------------

def _zero_pad_cols(w, cols: int):
    return jnp.pad(w, ((0, 0), (0, cols - w.shape[1]))) \
        if cols > w.shape[1] else w


def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    Hp, Hkvp = cfg.n_heads_padded, cfg.n_kv_heads_padded
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    so = 1.0 / math.sqrt(H * hd) / math.sqrt(2.0 * cfg.n_layers)
    # dead (padding) heads are ZERO so they contribute nothing and their
    # gradients are identically zero (see ModelConfig.head_pad_multiple)
    wq = _zero_pad_cols(ninit(ks[0], (d, H * hd), s, dtype), Hp * hd)
    wk = _zero_pad_cols(ninit(ks[1], (d, Hkv * hd), s, dtype), Hkvp * hd)
    wv = _zero_pad_cols(ninit(ks[2], (d, Hkv * hd), s, dtype), Hkvp * hd)
    wo = _zero_pad_cols(ninit(ks[3], (H * hd, d), so, dtype).T,
                        Hp * hd).T
    return {"wq": wq, "wk": wk, "wv": wv, "wo": wo}


def attention_specs(cfg: ModelConfig) -> Dict:
    return {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }


def _split_heads(x, n, hd):
    B, S, _ = x.shape
    return x.reshape(B, S, n, hd).transpose(0, 2, 1, 3)


def _merge_heads(x):
    B, H, S, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * hd)


def _full_attn(q, k, v, be: Policy, *, causal, window, q_offset, scale):
    if be.pallas:
        from repro.kernels import ops
        return ops.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, scale=scale,
                                   bq=min(128, q.shape[2]),
                                   interpret=be.interpret)
    return ref.chunked_mha(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, scale=scale,
                           kv_chunk=min(1024, k.shape[2]))


def paged_attend(q, k_pool, v_pool, block_table, q_pos, *,
                 scale: float, window: Optional[int] = None,
                 decode_from=None):
    """Attention over a paged KV pool, read through a block table.

    q: (B, H, C, hd); k_pool/v_pool: (P, Hkv, BS, hd) — one layer's
    block pool; block_table: (B, nmax) int32 pool ids in *logical*
    order (padded with the null block 0); q_pos: (B, C) absolute query
    positions.  Because the table lists blocks logically, flattened key
    index j of the gathered (B, Hkv, nmax*BS, hd) buffer holds sequence
    position j — the mask is simply ``j <= q_pos`` (causal over the
    request's own history; stale/pad slots beyond ``q_pos`` and other
    requests' blocks are unreachable by construction).

    Two numerics recipes: normalised-probs rounding for decode tokens
    (C == 1) and flash-style unnormalised accumulation for prefill rows
    (``ref.chunked_mha``'s order); masked lanes contribute exact zeros
    either way.  ``decode_from`` (B,) marks where the request's ORIGINAL
    decode boundary sits: a recompute-resume chunk replays positions
    that the unpreempted run processed one token at a time, so rows at
    ``q_pos >= decode_from`` select the decode numerics even inside a
    C > 1 chunk.  That is what makes preempted requests replay
    bit-identically: without it the replayed rows pick up flash-vs-
    softmax rounding, the recurrent carries inherit it, and the
    continuation after preemption drifts from the unpreempted one."""
    B, H, C, hd = q.shape
    Hkv, BS = k_pool.shape[1], k_pool.shape[2]
    nmax = block_table.shape[1]
    rep = H // Hkv
    # gather the request's blocks: (B, nmax, Hkv, BS, hd) -> (B, Hkv, S, hd)
    kg = k_pool[block_table].transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hkv, nmax * BS, hd)
    vg = v_pool[block_table].transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hkv, nmax * BS, hd)
    key_pos = jnp.arange(nmax * BS)
    ok = key_pos[None, None, :] <= q_pos[:, :, None]          # (B, C, S)
    if window is not None:
        ok &= key_pos[None, None, :] > q_pos[:, :, None] - window
    if C == 1:
        # decode: grouped-GQA, normalised-softmax order
        qf = q.reshape(B, Hkv, rep, hd)
        logits = jnp.einsum("bkrd,bksd->bkrs", qf, kg,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(ok[:, None, None, 0, :], logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkrs,bksd->bkrd", p.astype(vg.dtype), vg,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, H, 1, hd).astype(q.dtype)
    # prefill chunk: chunked_mha's repeated-KV, unnormalised-exp order
    kb = jnp.repeat(kg, rep, axis=1)
    vb = jnp.repeat(vg, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, None], s, -jnp.inf)
    m = s.max(-1)                      # rows always see >= 1 valid key
    p = jnp.exp(s - m[..., None])
    p = jnp.where(ok[:, None], p, 0.0)
    l = p.sum(-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32)
    flash = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(q.dtype)
    if decode_from is None:
        return flash
    # recompute-resume: replayed decode rows take the C == 1 branch's
    # op-for-op numerics (same grouped-GQA shapes, batched over C), so
    # preempted requests replay bit-identically
    qf = q.reshape(B, Hkv, rep, C, hd)
    logits = jnp.einsum("bkrqd,bksd->bkrqs", qf, kg,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(ok[:, None, None], logits, -jnp.inf)
    pd = jax.nn.softmax(logits, axis=-1)
    outd = jnp.einsum("bkrqs,bksd->bkrqd", pd.astype(vg.dtype), vg,
                      preferred_element_type=jnp.float32)
    outd = outd.reshape(B, H, C, hd).astype(q.dtype)
    replay = q_pos >= decode_from[:, None]                    # (B, C)
    return jnp.where(replay[:, None, :, None], outd, flash)


def attention(p: Dict, x, be: Policy, cfg: ModelConfig, *,
              causal: bool = True, window: Optional[int] = None,
              positions=None, cross_kv: Optional[Tuple] = None,
              paged_kv: Optional[Tuple] = None):
    """Unified attention layer.

    Modes:
      sequence: positions (S,) or (B,S); the whole sequence at once
                (training, the encoder).
      paged:    paged_kv (k_pool, v_pool, block_table, pos (B,C));
                writes the chunk through the table, attends via the
                gather path; one code path serves chunked prefill (C>1)
                and slot decode (C=1).
      cross:    cross_kv (k, v) precomputed from encoder states.
    Returns y, and in paged mode (y, (k_pool, v_pool))."""
    H, Hkv, hd = cfg.n_heads_padded, cfg.n_kv_heads_padded, cfg.head_dim_
    scale = hd ** -0.5
    B, S, _ = x.shape
    q = _split_heads(mm(x, p["wq"], be), H, hd)
    if cross_kv is not None:
        k, v = cross_kv
        y = _full_attn(q, k, v, be, causal=False, window=None, q_offset=0,
                       scale=scale)
        return mm(_merge_heads(y), p["wo"], be)
    q = constrain(q, "batch", "heads", None, None)
    k = _split_heads(mm(x, p["wk"], be), Hkv, hd)
    v = _split_heads(mm(x, p["wv"], be), Hkv, hd)
    k = constrain(k, "batch", "kv", None, None)
    v = constrain(v, "batch", "kv", None, None)
    if paged_kv is not None:
        # paged: rope at absolute positions, write the chunk through the
        # block table, attend over the gathered pool
        if len(paged_kv) == 5:
            k_pool, v_pool, bt, qpos, decode_from = paged_kv
        else:
            k_pool, v_pool, bt, qpos = paged_kv
            decode_from = None
        BS = k_pool.shape[2]
        q = rope(q, qpos, cfg.rope_theta)
        k = rope(k, qpos, cfg.rope_theta)
        blk = jnp.take_along_axis(bt, (qpos // BS).astype(jnp.int32),
                                  axis=1)                     # (B, C)
        off = jnp.mod(qpos, BS).astype(jnp.int32)
        # advanced indices at dims 0 and 2 -> update shape (B, C, Hkv, hd)
        k_pool = k_pool.at[blk, :, off, :].set(
            k.transpose(0, 2, 1, 3).astype(k_pool.dtype))
        v_pool = v_pool.at[blk, :, off, :].set(
            v.transpose(0, 2, 1, 3).astype(v_pool.dtype))
        y = paged_attend(q, k_pool, v_pool, bt, qpos, window=window,
                         scale=scale, decode_from=decode_from)
        return mm(_merge_heads(y), p["wo"], be), (k_pool, v_pool)
    if positions is None:
        positions = jnp.arange(S)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    y = _full_attn(q, k, v, be, causal=causal, window=window, q_offset=0,
                   scale=scale)
    return mm(_merge_heads(y), p["wo"], be)


# --------------------------------------------------------------------------
# Gated MLP (SwiGLU).
# --------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None,
             dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(d)
    sd = 1.0 / math.sqrt(ff) / math.sqrt(2.0 * cfg.n_layers)
    return {"wg": ninit(ks[0], (d, ff), s, dtype),
            "wu": ninit(ks[1], (d, ff), s, dtype),
            "wd": ninit(ks[2], (ff, d), sd, dtype)}


def mlp_specs(cfg: ModelConfig) -> Dict:
    return {"wg": ("embed", "mlp"), "wu": ("embed", "mlp"),
            "wd": ("mlp", "embed")}


def mlp(p: Dict, x, be: Policy):
    h = jax.nn.silu(mm(x, p["wg"], be)) * mm(x, p["wu"], be)
    h = constrain(h, "batch", None, "mlp")
    return mm(h, p["wd"], be)


# --------------------------------------------------------------------------
# MoE: top-k routing, sort-based capacity dispatch, grouped small GEMM.
# --------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    m = cfg.moe
    d, E, f = cfg.d_model, m.num_experts, m.d_expert
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    sd = 1.0 / math.sqrt(f) / math.sqrt(2.0 * cfg.n_layers)
    return {
        "router": ninit(ks[0], (d, E), s, jnp.float32),
        "w_gate": ninit(ks[1], (E, d, f), s, dtype),
        "w_up": ninit(ks[2], (E, d, f), s, dtype),
        "w_down": ninit(ks[3], (E, f, d), sd, dtype),
    }


def moe_specs(cfg: ModelConfig) -> Dict:
    return {"router": ("embed", None),
            "w_gate": ("experts", "embed", "expert_mlp"),
            "w_up": ("experts", "embed", "expert_mlp"),
            "w_down": ("experts", "expert_mlp", "embed")}


#: MoE leaves read in float32: the router's logits are computed in
#: float32 (:func:`_moe_dispatch`).  Attention and MLP weights, like the
#: experts, are consumed in the compute dtype only.
MOE_F32_LEAVES = frozenset({"router"})


def _capacity(T: int, m) -> int:
    c = int(math.ceil(T * m.top_k / m.num_experts * m.capacity_factor))
    # 128-multiples: MXU-aligned AND divisible by the data axis so the
    # (E, C, d) dispatch buffer shards its capacity dim
    grain = 128 if c >= 128 else 8
    return max(grain, -(c // -grain) * grain)


def _moe_dispatch(router, xf, cfg: ModelConfig, C: int):
    """Route + sort + capacity for one token shard.  xf: (T, d).

    Returns (buf (E, C, d), combine metadata, aux).  Gather-only data
    movement: the ONLY scatters are int32 slot maps (a (T*k, d) row
    scatter lowers to a per-element sort on some backends — measured
    7.5 GiB u32 temps)."""
    m = cfg.moe
    T, d = xf.shape
    E, k = m.num_experts, m.top_k

    logits = jnp.matmul(xf.astype(jnp.float32), router)           # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, k)                            # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                                    # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    stok = (jnp.arange(T * k) // k)[order]
    counts = jnp.bincount(flat_e, length=E)                       # (E,)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * k) - starts[se]
    keep = rank < C
    dest = jnp.where(keep, se * C + rank, E * C)                  # OOB=drop

    inv = jnp.zeros((E * C + 1,), jnp.int32).at[dest].set(
        stok, mode="drop")                                        # slot->token
    filled = jnp.zeros((E * C + 1,), jnp.bool_).at[dest].set(
        keep, mode="drop")
    buf = jnp.where(filled[:E * C, None],
                    xf.at[inv[:E * C]].get(mode="clip"), 0)
    slot_flat = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.where(keep, dest, E * C).astype(jnp.int32))           # (T*k,)

    me = probs.mean(0)                                            # (E,)
    ce = (counts / jnp.maximum(counts.sum(), 1)).astype(jnp.float32)
    aux = m.aux_loss * E * jnp.sum(me * ce) \
        + m.router_z_loss * jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return buf.reshape(E, C, d), (slot_flat, top_p), aux


def _moe_combine(out_buf, meta, T: int, k: int):
    """Per-token gather of its k expert rows (no (T, d) scatter); the
    weighted sum runs in bf16 with an f32 accumulator so any cross-shard
    reduction moves bf16, not f32."""
    slot_flat, top_p = meta
    EC, d = out_buf.shape[0] * out_buf.shape[1], out_buf.shape[2]
    rows = out_buf.reshape(EC, d).at[slot_flat].get(
        mode="fill", fill_value=0).reshape(T, k, d)
    # plain (non-f32-accumulated) einsum: k <= 8 terms, and an f32
    # preferred type would make the rows cotangent f32 — doubling the EP
    # combine all-reduce
    return jnp.einsum("tkd,tk->td", rows, top_p.astype(rows.dtype))


def _expert_ffn(p, buf, be: Policy, x_dtype):
    """(…, E, C, d) @ experts — grouped small GEMMs (the paper's habitat).

    The 3-D (per-shard) case routes each grouped product through
    ``api.batched_gemm``, so the per-group (C, K, N) problem gets the
    same input-aware, profile-refined treatment as the 2-D path (XLA
    einsum when the router declines pallas)."""
    wg = p["w_gate"].astype(x_dtype)
    wu = p["w_up"].astype(x_dtype)
    wd = p["w_down"].astype(x_dtype)
    if buf.ndim == 3 and be.pallas:
        from repro import api
        h = (jax.nn.silu(api.batched_gemm(buf, wg, policy=be))
             * api.batched_gemm(buf, wu, policy=be))
        return api.batched_gemm(h, wd, policy=be)
    eq = "ecd,edf->ecf" if buf.ndim == 3 else "gecd,edf->gecf"
    eq2 = "ecf,efd->ecd" if buf.ndim == 3 else "gecf,efd->gecd"
    h = jax.nn.silu(jnp.einsum(eq, buf, wg)) * jnp.einsum(eq, buf, wu)
    if buf.ndim == 4:
        h = constrain(h, "moe_group", "experts", None, "expert_mlp")
    out = jnp.einsum(eq2, h, wd)
    if buf.ndim == 4:
        out = constrain(out, "moe_group", "experts", None, None)
    return out


def moe(p: Dict, x, be: Policy, cfg: ModelConfig):
    """x: (B, S, d) -> (y, aux).

    §Perf iteration 2/4 (beyond-paper): dispatch and combine run PER DATA
    SHARD via a vmapped leading group axis sized to the data-parallel
    degree; the group axis is sharded over "data" so routing / sort /
    capacity / token gathers are embarrassingly parallel (zero cross-device
    token movement; capacity is per-shard, the standard per-device
    semantics).  The expert FFN itself runs OUTSIDE the vmap on the
    (G, E, C, d) buffer with explicit shardings: E over model (EP,
    moonshot) or the expert hidden dim over model (TP, mixtral)."""
    from repro.parallel.ctx import moe_shard_count
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    k = m.top_k
    G = moe_shard_count()
    if G <= 1 or T % G or (T // G) % 8:
        buf, meta, aux = _moe_dispatch(p["router"], x.reshape(T, d), cfg,
                                       _capacity(T, m))
        out_buf = _expert_ffn(p, buf, be, x.dtype)
        y = _moe_combine(out_buf, meta, T, k)
        return y.astype(x.dtype).reshape(B, S, d), aux
    T_loc = T // G
    C = _capacity(T_loc, m)
    xg = constrain(x.reshape(G, T_loc, d), "moe_group", None, None)
    buf, meta, aux = jax.vmap(
        lambda xs: _moe_dispatch(p["router"], xs, cfg, C))(xg)
    buf = constrain(buf, "moe_group", "experts", None, None)
    slot = constrain(meta[0], "moe_group", None)
    top_p = constrain(meta[1], "moe_group", None, None)
    out_buf = _expert_ffn(p, buf, be, x.dtype)
    yg = jax.vmap(lambda ob, sl, tp: _moe_combine(ob, (sl, tp), T_loc, k))(
        out_buf, slot, top_p)
    yg = constrain(yg, "moe_group", None, None)
    return yg.astype(x.dtype).reshape(B, S, d), aux.mean()
