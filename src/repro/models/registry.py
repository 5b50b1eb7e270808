"""Model registry: one uniform interface over all backbone families.

``Model`` bundles init/specs/apply closures so the launcher, trainer and
server never branch on family."""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, lm


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable                    # (key) -> params
    specs: Callable                   # () -> logical-axis tree
    forward_train: Callable           # (params, batch, be) -> (logits, aux)
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

        return Model(cfg, lambda key: encdec.init_encdec(key, cfg),
                     lambda: encdec.encdec_specs(cfg), fwd)

    def fwd(params, batch, be):
        return lm.forward_train(params, cfg, be, batch["tokens"],
                                batch.get("prefix_embeds"))

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
                 lambda: lm.lm_specs(cfg), fwd,
                 paged_prefill=ppf, paged_decode=pdec,
                 init_paged_state=mk_ps,
                 serving_params=lambda p: lm.serving_params(p, cfg))
