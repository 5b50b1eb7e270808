"""Encoder-decoder backbone (seamless-m4t-v2 assignment entry).

The audio frontend is a STUB per the assignment: the encoder consumes
precomputed frame embeddings (B, S_src, d) from ``input_specs``.  The
decoder is a causal LM stack with cross-attention into the encoder states.
Only the teacher-forced forward exists: the family has no serving path
(``Model.paged_decode`` is None and ``PagedEngine`` refuses it).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.api import Policy
from repro.models.common import (mm, ninit, rmsnorm, stack_init,
                                 stack_specs)
from repro.models.lm import _remat


def _norm(cfg, dtype):
    return jnp.ones((cfg.d_model,), dtype) if cfg.parametric_norm else None


def _init_enc_block(cfg: ModelConfig, dtype):
    def init(key):
        ks = jax.random.split(key, 2)
        return {"ln1": _norm(cfg, dtype),
                "attn": L.init_attention(ks[0], cfg, dtype),
                "ln2": _norm(cfg, dtype),
                "mlp": L.init_mlp(ks[1], cfg, dtype=dtype)}
    return init


def _init_dec_block(cfg: ModelConfig, dtype):
    def init(key):
        ks = jax.random.split(key, 3)
        return {"ln1": _norm(cfg, dtype),
                "self_attn": L.init_attention(ks[0], cfg, dtype),
                "ln_x": _norm(cfg, dtype),
                "cross_attn": L.init_attention(ks[1], cfg, dtype),
                "ln2": _norm(cfg, dtype),
                "mlp": L.init_mlp(ks[2], cfg, dtype=dtype)}
    return init


def init_encdec(key, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    d, Vp = cfg.d_model, cfg.vocab_padded
    return {
        "embed": ninit(ks[0], (Vp, d), d ** -0.5, dtype),
        "enc_blocks": stack_init(_init_enc_block(cfg, dtype), ks[1],
                                 cfg.n_encoder_layers),
        "enc_norm": _norm(cfg, dtype),
        "dec_blocks": stack_init(_init_dec_block(cfg, dtype), ks[2],
                                 cfg.n_layers),
        "final_norm": _norm(cfg, dtype),
        "unembed": ninit(ks[3], (d, Vp), 1.0 / math.sqrt(d), dtype),
    }


def encdec_specs(cfg: ModelConfig) -> Dict:
    n = ("embed",) if cfg.parametric_norm else None
    a = L.attention_specs(cfg)
    m = L.mlp_specs(cfg)
    return {
        "embed": ("vocab", None),
        "enc_blocks": stack_specs({"ln1": n, "attn": a, "ln2": n, "mlp": m}),
        "enc_norm": n,
        "dec_blocks": stack_specs({"ln1": n, "self_attn": a, "ln_x": n,
                                   "cross_attn": a, "ln2": n, "mlp": m}),
        "final_norm": n,
        "unembed": (None, "vocab"),
    }


def encode(params, cfg: ModelConfig, be: Policy, src_embeds) -> jax.Array:
    """src_embeds: (B, S_src, d) (stubbed frontend output)."""
    x = src_embeds.astype(cfg.compute_dtype)
    positions = jnp.arange(x.shape[1])

    def body(x, blk):
        h = rmsnorm(x, blk["ln1"], cfg.norm_eps)
        x = x + L.attention(blk["attn"], h, be, cfg, causal=False,
                            positions=positions)
        h = rmsnorm(x, blk["ln2"], cfg.norm_eps)
        return x + L.mlp(blk["mlp"], h, be), None

    x, _ = lax.scan(_remat(body, cfg), x, params["enc_blocks"])
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps)


def _cross_kv(blk, enc, cfg, be):
    Hkv, hd = cfg.n_kv_heads_padded, cfg.head_dim_
    B, Ssrc, _ = enc.shape
    k = mm(enc, blk["cross_attn"]["wk"], be).reshape(
        B, Ssrc, Hkv, hd).transpose(0, 2, 1, 3)
    v = mm(enc, blk["cross_attn"]["wv"], be).reshape(
        B, Ssrc, Hkv, hd).transpose(0, 2, 1, 3)
    return k, v


def _dec_block(blk, x, enc, cfg, be, *, positions=None):
    h = rmsnorm(x, blk["ln1"], cfg.norm_eps)
    x = x + L.attention(blk["self_attn"], h, be, cfg, causal=True,
                        positions=positions)
    h = rmsnorm(x, blk["ln_x"], cfg.norm_eps)
    x = x + L.attention(blk["cross_attn"], h, be, cfg,
                        cross_kv=_cross_kv(blk, enc, cfg, be))
    h = rmsnorm(x, blk["ln2"], cfg.norm_eps)
    return x + L.mlp(blk["mlp"], h, be)


def forward_train(params, cfg: ModelConfig, be: Policy, tokens,
                  src_embeds) -> Tuple[jax.Array, jax.Array]:
    """Teacher-forced training: (logits (B, S_tgt, Vp), aux=0)."""
    enc = encode(params, cfg, be, src_embeds)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    positions = jnp.arange(x.shape[1])

    def body(x, blk):
        return _dec_block(blk, x, enc, cfg, be, positions=positions), None

    x, _ = lax.scan(_remat(body, cfg), x, params["dec_blocks"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return mm(x, params["unembed"], be), jnp.zeros((), jnp.float32)
