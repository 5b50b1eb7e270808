"""Per-architecture smoke tests (deliverable f): reduced same-family
configs, one forward + one train step on CPU, asserting shapes + no NaNs;
plus serving-path consistency and param/spec structure invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import frontends, registry
from repro.models.common import XLA, assert_same_structure, count_params
from repro.train import loop as TL

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=24, key=KEY, with_labels=False):
    tok = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": tok}
    if cfg.frontend == "vision":
        batch["tokens"] = tok[:, :S - cfg.frontend_tokens]
        batch["prefix_embeds"] = frontends.fake_frontend(key, cfg, B, S,
                                                         jnp.float32)
    if cfg.frontend == "audio":
        batch["src_embeds"] = frontends.fake_frontend(key, cfg, B, S,
                                                      jnp.float32)
    if with_labels:
        batch["labels"] = jax.random.randint(
            jax.random.PRNGKey(7), batch["tokens"].shape, 0, cfg.vocab)
    return batch


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_smoke_forward(arch):
    cfg = configs.get_smoke(arch)
    model = registry.build(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    logits, aux = model.forward_train(params, batch, XLA)
    S_out = 24 if cfg.frontend != "vision" else 24
    assert logits.shape == (2, S_out, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = dataclasses.replace(configs.get_smoke(arch), remat="none")
    model = registry.build(cfg)
    state = TL.init_train_state(model, KEY)
    step = TL.make_train_step(model, TL.TrainConfig(), XLA)
    batch = _batch(cfg, with_labels=True)
    state2, m = jax.jit(step)(state, batch)
    assert bool(jnp.isfinite(m["loss"]))
    assert bool(jnp.isfinite(m["grad_norm"]))
    assert int(state2["step"]) == 1
    # params actually changed (bitwise: warmup lr steps are tiny)
    d0 = jax.tree.leaves(state["params"])[0]
    d1 = jax.tree.leaves(state2["params"])[0]
    assert not bool((d0 == d1).all())


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_param_spec_structures_match(arch):
    cfg = configs.get_smoke(arch)
    model = registry.build(cfg)
    params = jax.eval_shape(model.init, KEY)
    assert_same_structure(params, model.specs())


# the families with a serving path: every one but encoder-decoder
DECODER_ONLY = [a for a in configs.ARCH_IDS
                if configs.get_config(a).family not in ("encdec", "audio")]


@pytest.mark.parametrize("arch", DECODER_ONLY)
def test_prefill_decode_consistency(arch):
    """The serving path (one slot: a prompt in one paged prefill chunk,
    then one paged decode step) against ``forward_train`` over the same
    tokens.  The VLM runs without a modality prefix: the paged path
    takes none."""
    cfg = dataclasses.replace(configs.get_smoke(arch), dtype="float32")
    model = registry.build(cfg)
    params = model.init(KEY)
    S, BS, NMAX = 17, 8, 3
    tok = jax.random.randint(KEY, (1, S + 1), 0, cfg.vocab)
    logits_full, _ = model.forward_train(params, {"tokens": tok}, XLA)
    ps = model.init_paged_state(1 + NMAX, BS, 1, jnp.float32)
    table = jnp.arange(1, NMAX + 1, dtype=jnp.int32)[None]
    lp, ps = model.paged_prefill(params, {"tokens": tok[:, :S]}, ps, table,
                                 jnp.zeros((1,), jnp.int32), 0, S, S, XLA)
    ld, _ = model.paged_decode(params, {"tokens": tok[:, S:]}, ps, table,
                               jnp.full((1,), S, jnp.int32),
                               jnp.ones((1,), bool), XLA)
    scale = float(jnp.abs(logits_full).max()) + 1e-6
    assert float(jnp.abs(lp - logits_full[:, :S]).max()) / scale < 1e-4
    assert float(jnp.abs(ld[:, 0] - logits_full[:, S]).max()) / scale < 1e-4


def test_full_configs_match_assignment():
    """The exact assigned numbers (deliverable f spot checks)."""
    c = configs.get_config("mixtral-8x22b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads) == (56, 6144, 48, 8)
    assert c.moe.num_experts == 8 and c.moe.top_k == 2
    c = configs.get_config("moonshot-v1-16b-a3b")
    assert c.moe.num_experts == 64 and c.moe.top_k == 6 and c.vocab == 163840
    c = configs.get_config("mamba2-780m")
    assert c.ssm.d_state == 128 and c.n_layers == 48 and c.d_model == 1536
    c = configs.get_config("zamba2-7b")
    assert c.n_layers == 81 and c.ssm.d_state == 64 and c.shared_attn_every
    c = configs.get_config("glm4-9b")
    assert (c.n_layers, c.d_model, c.n_kv_heads, c.d_ff) == (40, 4096, 2, 13696)
    c = configs.get_config("gemma3-1b")
    assert c.attn.local_ratio == 5 and c.vocab == 262144
    c = configs.get_config("olmo-1b")
    assert not c.parametric_norm and c.vocab == 50304
    c = configs.get_config("smollm-360m")
    assert (c.n_heads, c.n_kv_heads, c.d_model) == (15, 5, 960)
    c = configs.get_config("seamless-m4t-large-v2")
    assert c.n_encoder_layers == 24 and c.vocab == 256206
    c = configs.get_config("internvl2-2b")
    assert c.frontend == "vision" and c.vocab == 92553


def test_head_padding_is_exact():
    """Zero-padded dead heads: identical logits, zero dead grads."""
    import numpy as np
    cfg0 = dataclasses.replace(configs.get_smoke("smollm-360m"),
                               dtype="float32", head_pad_multiple=0)
    cfg1 = dataclasses.replace(cfg0, head_pad_multiple=4)
    m0, m1 = registry.build(cfg0), registry.build(cfg1)
    p0, p1 = m0.init(KEY), m1.init(KEY)

    def pad_like(a, b):
        out = np.zeros(b.shape, np.float32)
        out[tuple(slice(0, s) for s in a.shape)] = np.asarray(a)
        return jnp.asarray(out)

    p1["blocks"]["attn"] = {k: pad_like(p0["blocks"]["attn"][k],
                                        p1["blocks"]["attn"][k])
                            for k in p1["blocks"]["attn"]}
    for k in ("embed", "final_norm"):
        p1[k] = p0[k]
    for k in ("ln1", "ln2", "mlp"):
        p1["blocks"][k] = p0["blocks"][k]
    tok = jax.random.randint(KEY, (2, 19), 0, cfg0.vocab)
    l0, _ = m0.forward_train(p0, {"tokens": tok}, XLA)
    l1, _ = m1.forward_train(p1, {"tokens": tok}, XLA)
    assert float(jnp.abs(l0 - l1).max()) == 0.0
    g = jax.grad(lambda p: (m1.forward_train(p, {"tokens": tok}, XLA)[0]
                            ** 2).sum())(p1)
    hd = cfg1.head_dim_
    assert float(jnp.abs(
        g["blocks"]["attn"]["wq"][:, :, cfg0.n_heads * hd:]).max()) == 0.0


def test_param_counts_sane():
    """Analytic count ~ actual count (MODEL_FLOPS denominator)."""
    for arch in ("olmo-1b", "glm4-9b", "mixtral-8x22b"):
        cfg = configs.get_config(arch)
        model = registry.build(cfg)
        actual = count_params(jax.eval_shape(model.init, KEY))
        analytic = cfg.param_count()
        assert abs(actual - analytic) / actual < 0.15, \
            (arch, actual, analytic)
