"""Model registry: one uniform interface over all backbone families.

``Model`` bundles init/specs/apply closures so the launcher, dry-run,
trainer and server never branch on family."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, lm


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable                    # (key) -> params
    specs: Callable                   # () -> logical-axis tree
    forward_train: Callable           # (params, batch, be) -> (logits, aux)
    prefill: Callable                 # (params, batch, be) -> (logits, cache)
    decode: Callable                  # (params, batch, cache, be) -> (logits, cache)
    init_cache: Callable              # (batch, seq_len) -> cache
    # paged serving path (repro.serve.PagedEngine): block-pool KV plus
    # per-slot recurrent carries, so EVERY decoder-only family serves
    # paged; None only for encoder-decoder archs
    paged_prefill: Optional[Callable] = None
    # ^ (params, batch, ps, tables, pos0, slot, seg_len, n_prompt, be)
    #   -> (logits, ps)
    paged_decode: Optional[Callable] = None
    # ^ (params, batch, ps, tables, pos, active, be) -> (logits, ps)
    init_paged_state: Optional[Callable] = None
    # ^ (num_blocks, block_size, slots, dtype) -> lm.PagedState
    serving_params: Optional[Callable] = None
    # ^ (params) -> params with the weights in compute_dtype
    #   (lm.serving_params); PagedEngine casts once at build


def build(cfg: ModelConfig) -> Model:
    if cfg.family == "encdec" or cfg.family == "audio":
        def fwd(params, batch, be):
            return encdec.forward_train(params, cfg, be, batch["tokens"],
                                        batch["src_embeds"])

        def pf(params, batch, be, cache_len=None):
            return encdec.prefill(params, cfg, be, batch["tokens"],
                                  batch["src_embeds"], cache_len=cache_len)

        def dec(params, batch, cache, be):
            return encdec.decode(params, cfg, be, batch["tokens"], cache)

        def mk_cache(batch, seq_len, dtype=jnp.bfloat16, src_len=None):
            return encdec.init_cache(cfg, batch, seq_len,
                                     src_len or seq_len, dtype,
                                     prefill_len=seq_len)

        return Model(cfg, lambda key: encdec.init_encdec(key, cfg),
                     lambda: encdec.encdec_specs(cfg), fwd, pf, dec,
                     mk_cache)

    def fwd(params, batch, be):
        return lm.forward_train(params, cfg, be, batch["tokens"],
                                batch.get("prefix_embeds"))

    def pf(params, batch, be, cache_len=None):
        return lm.prefill(params, cfg, be, batch["tokens"],
                          batch.get("prefix_embeds"), cache_len=cache_len)

    def dec(params, batch, cache, be):
        return lm.decode(params, cfg, be, batch["tokens"], cache)

    def mk_cache(batch, seq_len, dtype=jnp.bfloat16, prefill_len=None):
        return lm.init_cache(cfg, batch, seq_len, dtype,
                             prefill_len=seq_len if prefill_len is None
                             else prefill_len)

    def ppf(params, batch, ps, tables, pos0, slot, seg_len, n_prompt, be):
        return lm.paged_prefill(params, cfg, be, batch["tokens"], ps,
                                tables, pos0, slot, seg_len, n_prompt)

    def pdec(params, batch, ps, tables, pos, active, be):
        return lm.paged_decode(params, cfg, be, batch["tokens"], ps,
                               tables, pos, active)

    def mk_ps(num_blocks, block_size, slots, dtype=jnp.bfloat16):
        return lm.init_paged_state(cfg, num_blocks, block_size, slots,
                                   dtype)

    return Model(cfg, lambda key: lm.init_lm(key, cfg),
                 lambda: lm.lm_specs(cfg), fwd, pf, dec, mk_cache,
                 paged_prefill=ppf, paged_decode=pdec,
                 init_paged_state=mk_ps,
                 serving_params=lambda p: lm.serving_params(p, cfg))
