"""Train-step builder: mixed precision, microbatched gradient accumulation,
family-aware loss, optimizer fusion — the function the trainer jits and
runs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.api import Policy
from repro.models.registry import Model
from repro.train import optimizer as opt


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: opt.OptConfig = opt.OptConfig()
    accum_steps: int = 1               # microbatch gradient accumulation
    z_loss: float = 1e-4


def init_train_state(model: Model, key) -> Dict[str, Any]:
    params = model.init(key)
    return {"params": params, "opt": opt.init_opt_state(params),
            "step": jnp.zeros((), jnp.int32)}


def train_state_specs(model: Model) -> Dict[str, Any]:
    ps = model.specs()
    return {"params": ps, "opt": {"m": ps, "v": ps}, "step": ()}


def _xent(logits, labels, vocab: int, z_loss: float):
    """Masked cross-entropy in f32 + z-loss; labels == -1 are ignored."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, jnp.clip(labels, 0, vocab - 1)[..., None],
                             axis=-1)[..., 0]
    valid = (labels >= 0) & (labels < vocab)
    per_tok = (lse - ll) + z_loss * lse ** 2
    per_tok = jnp.where(valid, per_tok, 0.0)
    n = jnp.maximum(valid.sum(), 1)
    return per_tok.sum() / n, n


def record_step(step: int, metrics: Dict[str, float],
                dt_s: float) -> None:
    """Fold one *executed* train step into the obs registry (called by
    the launcher after the host has blocked on the step's metrics — a
    jit'd step cannot time itself).  ``BENCH`` exports and
    ``python -m repro.obs report`` read these."""
    obs.counter("train.steps").inc()
    obs.histogram("train.step_us").record(dt_s * 1e6)
    obs.gauge("train.step").set(step)
    if "loss" in metrics:
        obs.gauge("train.loss").set(float(metrics["loss"]))


def make_loss_fn(model: Model, tc: TrainConfig, be: Policy) -> Callable:
    cfg = model.cfg

    def loss_fn(params, batch):
        logits, aux = model.forward_train(params, batch, be)
        tokens = batch["tokens"]
        if cfg.frontend == "vision":
            logits = logits[:, cfg.frontend_tokens:]
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.concatenate(
                [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1)
        ce, n = _xent(logits, labels, cfg.vocab, tc.z_loss)
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux, "tokens": n}

    return loss_fn


def _split_micro(batch: Dict[str, jax.Array], accum: int):
    def sp(x):
        return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
    return jax.tree.map(sp, batch)


def cast_params_for_compute(params, dtype):
    """f32 master -> bf16 working copy, ONCE per step (outside the accum
    scan) so ZeRO-3 all-gathers inside the scan move bf16, not f32 —
    measured 2x collective-bytes reduction on the mixtral train cell.

    Precision-sensitive leaves stay f32: 1-D params (norms, A_log,
    dt_bias, D) and MoE router weights."""
    def cast(path, p):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if not jnp.issubdtype(p.dtype, jnp.floating):
            return p
        if p.ndim < 2 or "router" in name:
            return p
        return p.astype(dtype)
    return jax.tree_util.tree_map_with_path(cast, params)


def make_train_step(model: Model, tc: TrainConfig, be: Policy) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    With ``accum_steps > 1`` the global batch is split along the batch dim
    and gradients are accumulated in f32 via lax.scan (activation memory
    scales 1/accum — how the 141B mixtral train cell fits v5e HBM)."""
    loss_fn = make_loss_fn(model, tc, be)
    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        params = state["params"]
        pc = cast_params_for_compute(params, model.cfg.compute_dtype)
        if tc.accum_steps > 1:
            micro = _split_micro(batch, tc.accum_steps)
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(carry, mb):
                gsum, lsum = carry
                (loss, _), g = vg(pc, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, lsum + loss), None

            (gsum, lsum), _ = lax.scan(body, (g0, jnp.zeros(())), micro)
            grads = jax.tree.map(lambda g: g / tc.accum_steps, gsum)
            loss = lsum / tc.accum_steps
            metrics = {}
        else:
            (loss, metrics), grads = vg(pc, batch)
        new_params, new_opt, om = opt.adamw_update(
            params, grads, state["opt"], state["step"], tc.opt)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        out = {"loss": loss, **om}
        return new_state, out

    return train_step
