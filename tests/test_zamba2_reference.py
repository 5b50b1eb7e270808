"""zamba2-7b against the plain float32 reference (bench/reference/hybrid.py)
at the smoke preset, on the reference's own seeded weights: the whole-
sequence forward (``forward_train``), the paged serving steps (prefill
chunks, then decode through the cache) and a ``PagedEngine`` run must give
the reference's logits; the same model with one part of the mechanism
taken out (the adapters, the embedding in the shared blocks' input, the
split of B and C into groups) must not."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import common, hybrid
from repro import configs
from repro.models import lm
from repro.models.common import XLA
from repro.models.registry import build
from repro.serve import PagedEngine, Request

#: Widest logit difference allowed, as a share of the largest reference
#: logit (about 4 here).  Both sides compute in float32; the program's
#: chunked SSD, flash-style attention and GEMM order differ from the
#: reference's token-by-token recurrence and plain softmax by rounding
#: only: the sound model reads 2.3e-6 (forward_train) and 3.3e-6 (paged
#: steps) on the CPU, each ablated model 1.09 to 1.18, so TOL leaves
#: thirty times room above the first and the ablations miss a hundred
#: times TOL (asserted below).
TOL = 1e-4
PROMPT, T = 21, 32


def _setup(cfg):
    s = types.SimpleNamespace(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, d_ff=cfg.d_ff,
        vocab=cfg.vocab, ssm=dataclasses.asdict(cfg.ssm),
        hybrid_layers=list(cfg.hybrid_layers),
        n_shared_blocks=cfg.n_shared_blocks, adapter_rank=cfg.adapter_rank,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)
    model = build(cfg)
    key = jax.random.PRNGKey(3)
    params = common.make_params(hybrid.rules(s),
                                jax.eval_shape(model.init, key), key)
    return s, model, params


@pytest.fixture(scope="module")
def zamba():
    cfg = dataclasses.replace(configs.get_smoke("zamba2-7b"), dtype="float32")
    assert len(cfg.hybrid_apps) >= 2 and cfg.n_shared_blocks == 2
    assert cfg.ssm.n_groups == 2 and cfg.adapter_rank > 0
    s, model, params = _setup(cfg)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (1, T), 0,
                                         cfg.vocab), np.int32)
    with jax.default_matmul_precision("highest"):
        h = hybrid.hidden(params, s, jnp.asarray(toks), common.dot_f32)
        want = common.dot_f32(h, hybrid.unembedding(params))
    return cfg, model, params, toks, np.asarray(want[0, :, :cfg.vocab])


def _train_logits(model, params, toks):
    lg, _ = model.forward_train(params, {"tokens": jnp.asarray(toks)}, XLA)
    return np.asarray(lg[0, :, :model.cfg.vocab])


def _paged_logits(model, params, toks, chunk=8, block=8):
    """Prefill the first PROMPT tokens in chunks, then decode the rest
    one at a time through the cache, teacher-forced: the logits of every
    position."""
    cfg = model.cfg
    nmax = T // block
    ps = model.init_paged_state(1 + nmax, block, 1, jnp.float32)
    table = jnp.arange(1, nmax + 1, dtype=jnp.int32)[None]
    rows = []
    for p0 in range(0, PROMPT, chunk):
        seg = min(chunk, PROMPT - p0)
        tk = np.zeros((1, chunk), np.int32)
        tk[0, :seg] = toks[0, p0:p0 + seg]
        lg, ps = model.paged_prefill(params, {"tokens": jnp.asarray(tk)}, ps,
                                     table, jnp.array([p0], jnp.int32), 0,
                                     seg, PROMPT, XLA)
        rows.append(np.asarray(lg[0, :seg]))
    for t in range(PROMPT, T):
        lg, ps = model.paged_decode(params, {"tokens": jnp.asarray(
            toks[:, t:t + 1])}, ps, table, jnp.array([t], jnp.int32),
            jnp.ones((1,), bool), XLA)
        rows.append(np.asarray(lg[:, 0]))
    return np.concatenate(rows)[:, :cfg.vocab]


def _miss(got, want):
    return float(np.abs(got - want).max()) / max(1.0, np.abs(want).max())


@pytest.mark.parametrize("path", ["forward_train", "paged"])
def test_matches_reference(zamba, path):
    cfg, model, params, toks, want = zamba
    fn = _train_logits if path == "forward_train" else _paged_logits
    assert _miss(fn(model, params, toks), want) <= TOL


def test_engine_serves_reference_tokens(zamba):
    """Two requests through ``PagedEngine`` (two slots, chunked prefill,
    decode): each served token is the reference's best after its prefix,
    within TOL of the best logit."""
    cfg, model, params, toks, _ = zamba
    s = _setup(cfg)[0]
    e = PagedEngine(model, params, XLA, slots=2, max_len=T, eos=-1,
                    block_size=8, chunk=8)
    prompts = [toks[0, :PROMPT], toks[0, 3:17]]
    for rid, p in enumerate(prompts):
        e.submit(Request(rid, p, max_new=T - PROMPT))
    out = e.run()
    for rid, p in enumerate(prompts):
        full = np.zeros((1, T), np.int32)
        seq = np.concatenate([p, out[rid][:-1]])
        full[0, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            h = hybrid.hidden(params, s, jnp.asarray(full), common.dot_f32)
            lg = np.asarray(common.dot_f32(h, hybrid.unembedding(params)))
        lg = lg[0, len(p) - 1:len(p) - 1 + len(out[rid]), :cfg.vocab]
        gap = lg.max(1) - lg[np.arange(len(out[rid])), out[rid]]
        assert gap.max() <= TOL * max(1.0, np.abs(lg).max()), (rid, gap)


def _no_adapter(model, params, monkeypatch):
    apps = dict(params["apps"], lora_bg=jnp.zeros_like(
        params["apps"]["lora_bg"]), lora_bu=jnp.zeros_like(
        params["apps"]["lora_bu"]))
    return model, dict(params, apps=apps)


def _no_embedding(model, params, monkeypatch):
    """The shared blocks read concat(x, 0) in place of concat(x, e)."""
    block = lm._shared_block

    def without_e(params, cfg, be, b, j, x, e, **kw):
        return block(params, cfg, be, b, j, x, jnp.zeros_like(e), **kw)

    monkeypatch.setattr(lm, "_shared_block", without_e)
    return model, params


def _one_group(model, params, monkeypatch):
    """Every head reads the first group's B and C, and the gated norm
    spans all of d_inner: the program's one-group path, on the columns
    of the first group."""
    cfg = model.cfg
    di, N, G = cfg.d_inner, cfg.ssm.d_state, cfg.ssm.n_groups
    keep = np.r_[0:2 * di + N, 2 * di + G * N:2 * di + G * N + N,
                 2 * di + 2 * G * N:2 * di + 2 * G * N + cfg.ssm_heads]
    conv = np.r_[0:di + N, di + G * N:di + G * N + N]
    mix = dict(params["blocks"]["mixer"],
               in_proj=params["blocks"]["mixer"]["in_proj"][..., keep],
               conv_w=params["blocks"]["mixer"]["conv_w"][..., conv],
               conv_b=params["blocks"]["mixer"]["conv_b"][..., conv])
    one = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm,
                                                           n_groups=1))
    return build(one), dict(params, blocks=dict(params["blocks"],
                                                mixer=mix))


@pytest.mark.parametrize("path", ["forward_train", "paged"])
@pytest.mark.parametrize("ablate", [_no_adapter, _no_embedding, _one_group],
                         ids=["no_adapter", "no_embedding", "one_group"])
def test_ablated_model_misses_reference(zamba, monkeypatch, path, ablate):
    cfg, model, params, toks, want = zamba
    model, params = ablate(model, params, monkeypatch)
    fn = _train_logits if path == "forward_train" else _paged_logits
    assert _miss(fn(model, params, toks), want) > 100 * TOL
