"""Logical-axis -> mesh-axis sharding rules (DP / FSDP / TP / EP / SP).

Model code annotates parameters with *logical* axes ("embed", "heads",
"mlp", "vocab", "experts", ...).  This module maps them onto the physical
mesh with divisibility-aware fallbacks, implementing:

  TP    heads/kv_heads/mlp/expert_mlp/vocab/inner -> "model"
  EP    experts -> "model" when num_experts divides the axis (else the
        expert MLP dim takes the TP shard instead)
  FSDP  embed -> "data"  (ZeRO-3: params + optimizer state sharded over
        the data axis; XLA inserts the per-layer all-gathers inside the
        layer scan)
  DP    batch -> ("pod", "data") — the pod axis is *pure* DP so the only
        cross-pod traffic is one gradient reduce per step (DCN-friendly)

Indivisible cases (smollm's 15 heads, gemma3's 4 heads on a 16-way model
axis) fall back to replication for that tensor — recorded by
``Rules.report()``, which lists every fallback explicitly.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


@dataclasses.dataclass
class Rules:
    mesh: Mesh
    table: Dict[str, Optional[Tuple[str, ...]]]
    fallbacks: Dict[str, str]

    def spec(self, logical: Optional[Tuple]) -> P:
        if logical is None:
            return P()
        return P(*(self.table.get(ax) if isinstance(ax, str) else ax
                   for ax in logical))

    def sharding(self, logical: Optional[Tuple]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical))

    def tree_shardings(self, spec_tree):
        # tuples (incl. ()) are sharding specs; None marks an ABSENT param
        # (e.g. olmo's non-parametric norms) and must stay None so the
        # sharding tree matches the param tree structure exactly
        return jax.tree.map(self.sharding, spec_tree,
                            is_leaf=lambda s: isinstance(s, tuple))

    def report(self) -> str:
        lines = [f"{k} -> {v}" for k, v in sorted(self.table.items())]
        lines += [f"FALLBACK {k}: {v}" for k, v in sorted(self.fallbacks.items())]
        return "\n".join(lines)


def _axis(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def make_rules(cfg: ModelConfig, mesh: Mesh, *, fsdp: bool = True,
               shard_experts: bool = True) -> Rules:
    md = _axis(mesh, "model")
    dd = _axis(mesh, "data")
    t: Dict[str, Optional[Tuple[str, ...]]] = {"layers": None}
    fb: Dict[str, str] = {}

    def give(name: str, size: int, axis: str, reason_ok=True):
        ax = _axis(mesh, axis)
        if size and size % ax == 0 and reason_ok:
            t[name] = axis
        else:
            t[name] = None
            fb[name] = f"size {size} % {axis}({ax}) != 0 -> replicate"

    # TP axes
    H, Hkv, hd = cfg.n_heads_padded, cfg.n_kv_heads_padded, \
        (cfg.head_dim_ if cfg.n_heads else 0)
    give("heads", H * hd if H else 0, "model", reason_ok=H % md == 0 if H else False)
    give("kv_heads", Hkv * hd if Hkv else 0, "model",
         reason_ok=Hkv % md == 0 if Hkv else False)
    give("mlp", cfg.d_ff, "model")
    give("vocab", cfg.vocab_padded, "model")
    if cfg.ssm:
        give("inner", cfg.d_inner, "model")
        t["ssm_heads"] = None
    if cfg.moe:
        E, fe = cfg.moe.num_experts, cfg.moe.d_expert
        if shard_experts and E % md == 0:
            t["experts"] = "model"          # EP
            t["expert_mlp"] = None
        else:
            t["experts"] = None
            give("expert_mlp", fe, "model")
            if E % md:
                fb["experts"] = f"{E} experts % model({md}) != 0 -> TP on expert_mlp"
    # FSDP
    if fsdp and cfg.d_model % dd == 0:
        t["embed"] = "data"
    else:
        t["embed"] = None
        if fsdp:
            fb["embed"] = f"d_model {cfg.d_model} % data({dd}) != 0"
    return Rules(mesh, t, fb)


# --------------------------------------------------------------------------
# Input / cache shardings per shape cell.
# --------------------------------------------------------------------------

def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_spec(mesh: Mesh, global_batch: int) -> Optional[Tuple[str, ...]]:
    axes = batch_axes(mesh)
    n = 1
    for a in axes:
        n *= _axis(mesh, a)
    if axes and global_batch % n == 0:
        return axes
    # try data only
    if global_batch % _axis(mesh, "data") == 0:
        return ("data",)
    return None


def data_shardings(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                   rules: Rules) -> Dict[str, NamedSharding]:
    """NamedShardings for batch inputs (tokens/labels/embeds)."""
    b = batch_spec(mesh, shape.global_batch)
    tok = NamedSharding(mesh, P(b, None))
    emb = NamedSharding(mesh, P(b, None, None))
    return {"tokens": tok, "labels": tok, "prefix_embeds": emb,
            "src_embeds": emb}
