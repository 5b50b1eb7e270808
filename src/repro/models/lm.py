"""Decoder-only LM covering the dense / MoE / SSM / hybrid families.

One parameter schema + two entry points: ``forward_train`` over a whole
sequence, and the paged serving steps (``paged_prefill`` for one chunk of
one request, ``paged_decode`` for one token of every slot).  Both are
built on a ``lax.scan`` over stacked layer params (compile time stays
O(1) in depth — the 81-layer zamba2 and 56-layer mixtral depend on it).

Family wiring:
  dense / vlm   uniform [attn + mlp] blocks; attention pattern full /
                swa / local:global (per-layer lax.cond, both branches
                compiled once).
  moe           [attn + moe] blocks, aux loss accumulated in the carry.
  ssm           [mamba] blocks (attention-free).
  hybrid        [mamba] blocks + ONE shared [attn + mlp] block (zamba2
                style) applied every ``shared_attn_every`` layers; its
                params are closed over (true weight sharing), its KV pool
                is indexed per application.
VLM (internvl2) enters ``forward_train`` through ``prefix_embeds`` (the
stubbed ViT frontend); audio enc-dec lives in encdec.py.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.api import Policy
from repro.models.common import mm, ninit, rmsnorm, stack_init, stack_specs


# --------------------------------------------------------------------------
# Init / specs.
# --------------------------------------------------------------------------

def _norm_w(cfg: ModelConfig, dtype):
    return jnp.ones((cfg.d_model,), dtype) if cfg.parametric_norm else None


def _init_block(cfg: ModelConfig, dtype):
    def init(key):
        ks = jax.random.split(key, 2)
        if cfg.family in ("ssm", "hybrid"):
            return {"ln1": _norm_w(cfg, dtype),
                    "mixer": S.init_mamba(ks[0], cfg, dtype)}
        p = {"ln1": _norm_w(cfg, dtype),
             "attn": L.init_attention(ks[0], cfg, dtype),
             "ln2": _norm_w(cfg, dtype)}
        if cfg.family == "moe":
            p["moe"] = L.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg, dtype=dtype)
        return p
    return init


def _block_specs(cfg: ModelConfig):
    n = ("embed",) if cfg.parametric_norm else None
    if cfg.family in ("ssm", "hybrid"):
        return {"ln1": n, "mixer": S.mamba_specs(cfg)}
    sp = {"ln1": n, "attn": L.attention_specs(cfg), "ln2": n}
    if cfg.family == "moe":
        sp["moe"] = L.moe_specs(cfg)
    else:
        sp["mlp"] = L.mlp_specs(cfg)
    return sp


def init_lm(key, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    d, Vp = cfg.d_model, cfg.vocab_padded
    params: Dict[str, Any] = {
        "embed": ninit(ks[0], (Vp, d), d ** -0.5, dtype),
        "blocks": stack_init(_init_block(cfg, dtype), ks[1], cfg.n_layers),
        "final_norm": _norm_w(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = ninit(ks[2], (d, Vp), 1.0 / math.sqrt(d), dtype)
    if cfg.shared_attn_every:
        kk = jax.random.split(ks[3], 2)
        params["shared"] = {
            "ln1": _norm_w(cfg, dtype),
            "attn": L.init_attention(kk[0], cfg, dtype),
            "ln2": _norm_w(cfg, dtype),
            "mlp": L.init_mlp(kk[1], cfg, dtype=dtype),
        }
    return params


def lm_specs(cfg: ModelConfig) -> Dict:
    n = ("embed",) if cfg.parametric_norm else None
    # embed/unembed shard ONLY the vocab dim (model axis): FSDP-sharding
    # the d_model dim forced a d-contracting logits matmul => a (B,S,V)
    # psum over data, and an 'involuntary full rematerialization' reshard
    # on the gather (§Perf iteration 3); vocab-only sharding removes both
    specs: Dict[str, Any] = {
        "embed": ("vocab", None),
        "blocks": stack_specs(_block_specs(cfg)),
        "final_norm": n,
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = (None, "vocab")
    if cfg.shared_attn_every:
        specs["shared"] = {"ln1": n, "attn": L.attention_specs(cfg),
                           "ln2": n, "mlp": L.mlp_specs(cfg)}
    return specs


#: Leaves read in float32 whatever the compute dtype, by their key: the
#: norm scales (``rmsnorm`` multiplies in float32) and each family's own.
#: Every other floating leaf is consumed only after a cast to
#: ``compute_dtype``: ``common.mm``, the embedding gather, the experts.
F32_LEAVES = (frozenset({"ln1", "ln2", "final_norm"})
              | S.MAMBA_F32_LEAVES | L.MOE_F32_LEAVES)


def serving_params(params: Dict, cfg: ModelConfig) -> Dict:
    """The param tree in the dtypes the serving steps consume it in:
    every floating leaf outside :data:`F32_LEAVES` cast to
    ``compute_dtype`` in one jitted pass, the rest the same arrays.  A
    step's own ``w.astype(x.dtype)`` is then a no-op, so no step converts
    a weight stack again, and the logits are bit-for-bit those of the
    uncast tree.  The identity when ``param_dtype`` is the compute
    dtype."""
    dt = cfg.compute_dtype
    if jnp.dtype(cfg.param_dtype) == dt:
        return params
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    leaves = [p for _, p in flat]
    idx = [i for i, (path, p) in enumerate(flat)
           if path[-1].key not in F32_LEAVES
           and jnp.issubdtype(p.dtype, jnp.floating) and p.dtype != dt]
    cast = jax.jit(lambda ws: [w.astype(dt) for w in ws])(
        [leaves[i] for i in idx])
    for i, w in zip(idx, cast):
        leaves[i] = w
    return jax.tree_util.tree_unflatten(tree, leaves)


# --------------------------------------------------------------------------
# Block application (shared by all modes).
# --------------------------------------------------------------------------

def _window_for_layer(cfg: ModelConfig, i):
    """Static-pattern helper; returns (needs_cond, window)."""
    a = cfg.attn
    if a.kind == "swa":
        return False, a.window
    if a.kind == "local_global":
        return True, a.window
    return False, None


def _apply_attn_block(p, x, be, cfg, i, *, positions=None, paged_kv=None):
    """attention (+cond on local/global) + mlp/moe. Returns
    (y, aux, new (k_pool, v_pool) or None)."""
    needs_cond, win = _window_for_layer(cfg, i)
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)

    def run(window):
        return L.attention(p["attn"], h, be, cfg, causal=True, window=window,
                           positions=positions, paged_kv=paged_kv)

    if needs_cond:
        is_global = (i % (cfg.attn.local_ratio + 1)) == cfg.attn.local_ratio
        out = lax.cond(is_global, lambda: run(None), lambda: run(win))
    else:
        out = run(win)
    if paged_kv is not None:
        attn_out, kv_out = out
    else:
        attn_out, kv_out = out, None
    x = x + attn_out
    h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "moe":
        y, aux = L.moe(p["moe"], h2, be, cfg)
    else:
        y = L.mlp(p["mlp"], h2, be)
    return x + y, aux, kv_out


def _apply_mamba_block(p, x, be, cfg):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    return x + S.mamba(p["mixer"], h, be, cfg)


def _maybe_shared(params, x, be, cfg, i, *, positions=None):
    """Hybrid: apply the shared attn block when i % every == 0."""
    if not cfg.shared_attn_every:
        return x

    def apply(x):
        return _apply_attn_block(params["shared"], x, be, cfg, i,
                                 positions=positions)[0]

    return lax.cond(i % cfg.shared_attn_every == 0,
                    apply, lambda x: x, x)


# --------------------------------------------------------------------------
# Forward (train).
# --------------------------------------------------------------------------

def _embed_tokens(params, cfg, tokens, be, prefix_embeds=None):
    from repro.parallel.ctx import constrain
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cfg.compute_dtype), x],
                            axis=1)
    return constrain(x, "batch", None, None)


def _unembed(params, cfg, x, be: Policy):
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    return mm(x, w, be)


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def forward_train(params: Dict, cfg: ModelConfig, be: Policy,
                  tokens: jax.Array,
                  prefix_embeds: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S_text) -> (logits (B, S_total, Vp), aux_loss)."""
    x = _embed_tokens(params, cfg, tokens, be, prefix_embeds)
    B, Stot, _ = x.shape
    positions = jnp.arange(Stot)
    idxs = jnp.arange(cfg.n_layers)

    if cfg.family in ("ssm", "hybrid"):
        def body(carry, xs):
            x = carry
            blk, i = xs
            x = _maybe_shared(params, x, be, cfg, i, positions=positions)
            x = _apply_mamba_block(blk, x, be, cfg)
            return x, None
        x, _ = lax.scan(_remat(body, cfg), x, (params["blocks"], idxs))
        aux = jnp.zeros((), jnp.float32)
    else:
        def body(carry, xs):
            x, aux = carry
            blk, i = xs
            x, a, _ = _apply_attn_block(blk, x, be, cfg, i,
                                        positions=positions)
            return (x, aux + a), None
        (x, aux), _ = lax.scan(_remat(body, cfg),
                               (x, jnp.zeros((), jnp.float32)),
                               (params["blocks"], idxs))
        aux = aux / cfg.n_layers
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(params, cfg, x, be), aux


# --------------------------------------------------------------------------
# Paged serving (every family): block-pool KV + per-slot recurrent
# carries, one pytree threaded through chunked prefill AND slot decode.
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedState:
    """Device-side serving state for one PagedEngine instance.

    Attention K/V live in block pools indexed through block tables
    (token-proportional, block-granular, see repro.serve.paged);
    recurrent carries live in per-SLOT rows — fixed-size, allocated for
    the slot's lifetime, never per token.  Hybrid models add dedicated
    pools for the weight-shared attention block, one pool row per
    application.  Which request owns which slot row is host-side state
    (:class:`repro.serve.paged.SlotStateStore`)."""
    attn_k: Optional[jax.Array] = None    # (L, P, Hkv, BS, hd)
    attn_v: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None      # (L, slots, K-1, ch)
    ssm: Optional[jax.Array] = None       # (L, slots, nh, Phd, N) f32
    shared_k: Optional[jax.Array] = None  # (napps, P, Hkv, BS, hd)
    shared_v: Optional[jax.Array] = None

    def tree_flatten(self):
        return ((self.attn_k, self.attn_v, self.conv, self.ssm,
                 self.shared_k, self.shared_v), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _n_shared_apps(cfg: ModelConfig) -> int:
    return -(cfg.n_layers // -cfg.shared_attn_every) \
        if cfg.shared_attn_every else 0


def init_paged_state(cfg: ModelConfig, num_blocks: int, block_size: int,
                     slots: int, dtype=jnp.bfloat16) -> PagedState:
    """Zero serving state; block 0 of every pool is the null sink (see
    repro.serve.paged) — zero-init keeps it finite for the masked reads
    inactive slots discard.  Slot rows start zero and are re-zeroed
    inside the jit'd prefill step whenever a chunk starts at position 0
    (fresh admission or recompute-resume)."""
    Hkv, hd = cfg.n_kv_heads_padded, cfg.head_dim_
    pool = (num_blocks, Hkv, block_size, hd)
    kw: Dict[str, Any] = {}
    if cfg.family in ("dense", "moe", "vlm"):
        kw["attn_k"] = jnp.zeros((cfg.n_layers,) + pool, dtype)
        kw["attn_v"] = jnp.zeros((cfg.n_layers,) + pool, dtype)
    if cfg.family in ("ssm", "hybrid"):
        conv1, h1 = S.init_paged_state(cfg, slots, dtype)
        kw["conv"] = jnp.zeros((cfg.n_layers,) + conv1.shape, conv1.dtype)
        kw["ssm"] = jnp.zeros((cfg.n_layers,) + h1.shape, h1.dtype)
    if cfg.shared_attn_every:
        na = _n_shared_apps(cfg)
        kw["shared_k"] = jnp.zeros((na,) + pool, dtype)
        kw["shared_v"] = jnp.zeros((na,) + pool, dtype)
    return PagedState(**kw)


def _paged_core(params, cfg: ModelConfig, be: Policy, x, ps: PagedState,
                conv, ssm_h, block_tables, qpos, seg_len, active,
                decode_from=None):
    """Layer stack shared by paged prefill chunks and slot decode.
    ``conv``/``ssm_h`` are (L, B, ...) rows aligned with x's batch dim
    (callers slice/scatter the slot rows); they ride in the layer scan's
    carry and each layer reads its row and writes it back in place, so a
    donated state stack is updated where it lies rather than rebuilt as
    a stacked scan output and copied over.  K/V route through
    ``block_tables`` into the pools; ``decode_from`` (B,) marks the
    original decode boundary so recompute-resume chunks replay those
    rows with decode numerics (see layers.paged_attend and
    ssm.serve_ssd).  Returns
    (logits, ps-with-new-pools, conv', ssm')."""
    idxs = jnp.arange(cfg.n_layers)
    if cfg.family in ("ssm", "hybrid"):
        # chunk-local row of the decode boundary: the recurrence replays
        # the rows from there on with the decode step (ssm.serve_ssd)
        replay_from = None if decode_from is None \
            else decode_from - qpos[:, 0]

        def body(carry, xs):
            x, conv, ssm_h, sk, sv = carry
            blk, i = xs
            if cfg.shared_attn_every:
                app = i // cfg.shared_attn_every

                def apply(x, sk, sv):
                    y, _, kv = _apply_attn_block(
                        params["shared"], x, be, cfg, i,
                        paged_kv=(sk[app], sv[app], block_tables, qpos,
                                  decode_from))
                    return y, sk.at[app].set(kv[0]), sv.at[app].set(kv[1])

                x, sk, sv = lax.cond(i % cfg.shared_attn_every == 0,
                                     apply, lambda x, sk, sv: (x, sk, sv),
                                     x, sk, sv)
            h = rmsnorm(x, blk["ln1"], cfg.norm_eps)
            row = (lax.dynamic_index_in_dim(conv, i, 0, keepdims=False),
                   lax.dynamic_index_in_dim(ssm_h, i, 0, keepdims=False))
            y, (cv, hh) = S.paged_step(blk["mixer"], h, be, cfg, row,
                                       seg_len=seg_len, active=active,
                                       replay_from=replay_from)
            conv = lax.dynamic_update_index_in_dim(conv, cv, i, 0)
            ssm_h = lax.dynamic_update_index_in_dim(ssm_h, hh, i, 0)
            return (x + y, conv, ssm_h, sk, sv), None
        (x, conv_new, ssm_new, sk, sv), _ = lax.scan(
            body, (x, conv, ssm_h, ps.shared_k, ps.shared_v),
            (params["blocks"], idxs))
        ps = dataclasses.replace(ps, shared_k=sk, shared_v=sv)
    else:
        def body(carry, xs):
            x = carry
            blk, i, kp, vp = xs
            x, _, kv = _apply_attn_block(
                blk, x, be, cfg, i,
                paged_kv=(kp, vp, block_tables, qpos, decode_from))
            return x, kv
        x, (kps, vps) = lax.scan(body, x, (params["blocks"], idxs,
                                           ps.attn_k, ps.attn_v))
        ps = dataclasses.replace(ps, attn_k=kps, attn_v=vps)
        conv_new = ssm_new = None
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(params, cfg, x, be), ps, conv_new, ssm_new


def paged_prefill(params: Dict, cfg: ModelConfig, be: Policy,
                  tokens: jax.Array, ps: PagedState, block_tables,
                  pos_start, slot, seg_len,
                  n_prompt) -> Tuple[jax.Array, PagedState]:
    """One prefill chunk for ONE request occupying ``slot``: tokens
    (1, C) at absolute positions ``pos_start[0] + [0..C)`` (the tail
    past ``seg_len`` is padding and advances nothing), block_tables
    (1, nmax).  ``n_prompt`` is the request's prompt length: rows at
    positions >= n_prompt only exist on recompute-resume (they replay
    tokens that decode generated before the preemption) and take the
    decode-path numerics, in attention and in the SSM recurrence, so a
    preempted request replays its tokens bit-identically.

    When ``pos_start == 0`` — fresh admission OR recompute-resume after
    preemption — the slot's recurrent-carry rows are zero-reset inside
    this jit step, so state reset happens in automatic lockstep with
    the scheduler rewinding ``pos`` to 0; there is no separate host
    reset call to forget.  Returns (logits (1, C, Vp), ps)."""
    x = _embed_tokens(params, cfg, tokens, be)
    B, C, _ = x.shape
    qpos = pos_start[:, None] + jnp.arange(C)[None, :]        # (1, C)
    seg = jnp.full((B,), seg_len, jnp.int32)
    dfrom = jnp.full((B,), n_prompt, jnp.int32)
    conv = ssm_h = None
    if cfg.family in ("ssm", "hybrid"):
        conv = lax.dynamic_slice_in_dim(ps.conv, slot, 1, axis=1)
        ssm_h = lax.dynamic_slice_in_dim(ps.ssm, slot, 1, axis=1)
        fresh = pos_start[0] == 0
        conv = jnp.where(fresh, jnp.zeros_like(conv), conv)
        ssm_h = jnp.where(fresh, jnp.zeros_like(ssm_h), ssm_h)
    logits, ps, conv_new, ssm_new = _paged_core(
        params, cfg, be, x, ps, conv, ssm_h, block_tables, qpos, seg,
        None, dfrom)
    if conv_new is not None:
        ps = dataclasses.replace(
            ps,
            conv=lax.dynamic_update_slice_in_dim(ps.conv, conv_new,
                                                 slot, axis=1),
            ssm=lax.dynamic_update_slice_in_dim(ps.ssm, ssm_new,
                                                slot, axis=1))
    return logits, ps


def paged_decode(params: Dict, cfg: ModelConfig, be: Policy,
                 tokens: jax.Array, ps: PagedState, block_tables, pos,
                 active) -> Tuple[jax.Array, PagedState]:
    """One slot-level decode step over ALL slots: tokens (slots, 1),
    pos (slots,), active (slots,) bool.  Inactive rows (idle slots,
    slots mid-prefill) read/write the null block through their all-zero
    table row and keep their recurrent carries bitwise unchanged (see
    ssm.paged_step).  Returns (logits (slots, 1, Vp), ps)."""
    x = _embed_tokens(params, cfg, tokens, be)
    qpos = pos[:, None] + jnp.arange(x.shape[1])[None, :]     # (slots, 1)
    logits, ps, conv_new, ssm_new = _paged_core(
        params, cfg, be, x, ps, ps.conv, ps.ssm, block_tables, qpos,
        None, active)
    if conv_new is not None:
        ps = dataclasses.replace(ps, conv=conv_new, ssm=ssm_new)
    return logits, ps
