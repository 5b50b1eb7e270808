"""The serving weights: ``Model.serving_params`` casts every weight the
model consumes only in ``compute_dtype`` once, so the engine's steps
convert no weight, and the served logits stay bit-for-bit those of the
float32 master weights."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.models import lm, registry
from repro.models.common import XLA
from repro.serve import PagedEngine, paged_step_fns

KEY = jax.random.PRNGKey(0)
# one smoke arch per decoder-only family: dense, MoE, VLM, ssm, hybrid
ARCHS = ("olmo-1b", "moonshot-v1-16b-a3b", "internvl2-2b", "mamba2-780m",
         "zamba2-7b")
SLOTS, BLOCK, NMAX, CHUNK = 2, 8, 4, 8


@pytest.fixture(scope="module")
def get_model():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = configs.get_smoke(arch)
            model = registry.build(cfg)
            params = _jitter(model.init(KEY))
            cache[arch] = (cfg, model, params, model.serving_params(params))
        return cache[arch]

    return get


def _jitter(params):
    """Scale every float32 leaf by 1 + 0.1 N(0, 1): the norm scales start
    at one, which bfloat16 holds exactly, and would hide a cast of them."""
    flat, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(flat))
    return jax.tree_util.tree_unflatten(tree, [
        p * (1 + 0.1 * jax.random.normal(k, p.shape, p.dtype))
        if p.dtype == jnp.float32 else p for p, k in zip(flat, keys)])


def _leaves(params):
    return [(path[-1].key, path[0].key, p) for path, p in
            jax.tree_util.tree_flatten_with_path(params)[0]]


def _steps(model, params, cfg):
    """One prefill chunk into slot 0, then one decode step over both
    slots (slot 1 idle), through the engine's unjitted step functions;
    returns every output."""
    decode, prefill = paged_step_fns(model, XLA)
    ps = model.init_paged_state(1 + SLOTS * NMAX, BLOCK, SLOTS,
                                cfg.compute_dtype)
    rng = np.random.RandomState(7)
    n = 6
    toks = np.zeros((1, CHUNK), np.int32)
    toks[0, :n] = rng.randint(0, cfg.vocab, n)
    bt = np.zeros((SLOTS, NMAX), np.int32)
    bt[0] = np.arange(1, NMAX + 1)
    row, ps = prefill(params, jnp.asarray(toks), ps, jnp.asarray(bt[:1]),
                      jnp.asarray([0], jnp.int32), np.int32(0), np.int32(n),
                      np.int32(n), np.int32(n - 1))
    cur = jnp.asarray([int(jnp.argmax(row[:cfg.vocab])), 0], jnp.int32)
    pos = jnp.asarray([n, 0], jnp.int32)
    active = jnp.asarray([True, False])
    logits, _ = model.paged_decode(params, {"tokens": cur[:, None]}, ps,
                                   jnp.asarray(bt), pos, active, XLA)
    nxt, ps2, _ = decode(params, cur, ps, jnp.asarray(bt), pos, active,
                         jax.random.PRNGKey(3))
    return [row, ps, logits, nxt, ps2]


@pytest.mark.parametrize("arch", ARCHS)
def test_serving_params_bit_identical(get_model, arch):
    """Prefill and decode over the cast weights give the same logits,
    next tokens and serving state, bit for bit, as over float32."""
    cfg, model, params, served = get_model(arch)
    assert cfg.param_dtype != cfg.dtype
    want = jax.tree.leaves(_steps(model, params, cfg))
    got = jax.tree.leaves(_steps(model, served, cfg))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)


@pytest.mark.parametrize("arch", ARCHS)
def test_serving_params_dtypes(get_model, arch):
    """Leaves the family reads in float32 keep ``param_dtype`` and stay
    the same arrays; every other floating leaf is in ``compute_dtype``."""
    cfg, model, params, served = get_model(arch)
    n_kept = 0
    for (key, _, p), (_, _, s) in zip(_leaves(params), _leaves(served)):
        assert s.shape == p.shape
        if key in lm.F32_LEAVES:
            n_kept += 1
            assert s is p and s.dtype == jnp.dtype(cfg.param_dtype), key
        else:
            assert s.dtype == cfg.compute_dtype, key
    if cfg.family in ("ssm", "hybrid", "moe"):
        assert n_kept            # the family's own float32 leaves


@pytest.mark.parametrize("arch", ARCHS)
def test_serving_params_identity_in_compute_dtype(arch):
    cfg = dataclasses.replace(configs.get_smoke(arch), param_dtype="bfloat16")
    model = registry.build(cfg)
    params = jax.eval_shape(model.init, KEY)
    assert model.serving_params(params) is params


def _weight_shapes(params, served):
    """What a per-step cast of a weight converts: each cast leaf's shape,
    its per-layer slice (the layer scan's operand) and its transpose (the
    tied unembedding)."""
    shapes = set()
    for (_, top, p), (_, _, s) in zip(_leaves(params), _leaves(served)):
        if s.dtype != p.dtype:
            shapes |= {p.shape, p.shape[::-1]}
            if top == "blocks":
                shapes.add(p.shape[1:])
    return {"x".join(map(str, sh)) for sh in shapes}


def _f32_converts(text):
    return set(re.findall(r"stablehlo\.convert %\S+ : \(tensor<([0-9x]*)xf32>"
                          r"\) -> tensor<[0-9x]*xbf16>", text))


def _lowered(eng, params):
    cur = jnp.zeros((eng.slots,), jnp.int32)
    bt = jnp.zeros((eng.slots, eng.cache.nmax), jnp.int32)
    pos = jnp.zeros((eng.slots,), jnp.int32)
    dec = eng._decode_fn.lower(params, cur, eng._ps, bt, pos,
                               jnp.ones((eng.slots,), bool), eng.key)
    pre = eng._prefill_fn.lower(
        params, jnp.zeros((1, eng.chunk), jnp.int32), eng._ps, bt[:1],
        jnp.zeros((1,), jnp.int32), np.int32(0), np.int32(eng.chunk),
        np.int32(eng.chunk), np.int32(eng.chunk - 1))
    return dec.as_text() + pre.as_text()


@pytest.mark.parametrize("arch", ["mamba2-780m", "olmo-1b"])
def test_engine_steps_convert_no_weight(get_model, arch):
    """The engine's jitted decode and prefill, lowered with its own
    params, convert no float32 weight; lowered with the float32 tree the
    same check finds the casts.  The counters price the one cast."""
    cfg, model, params, served = get_model(arch)
    obs.reset()
    eng = PagedEngine(model, params, XLA, slots=SLOTS, max_len=24,
                      block_size=BLOCK, chunk=CHUNK)
    weights = _weight_shapes(params, served)
    assert weights and _f32_converts(_lowered(eng, params)) & weights
    assert not _f32_converts(_lowered(eng, eng.params)) & weights

    cast = kept = 0
    for (_, _, p), (_, _, s) in zip(_leaves(params), _leaves(eng.params)):
        if s.dtype != p.dtype:
            cast += s.size * jnp.dtype(jnp.bfloat16).itemsize
        elif jnp.issubdtype(p.dtype, jnp.floating):
            kept += p.size * p.dtype.itemsize
    assert cast and obs.counter("serve.params_cast_bytes").value == cast
    assert obs.counter("serve.params_kept_bytes").value == kept
    spans = [e[4][0] for e in obs.TRACE.snapshot() if e[1] == "SPAN"]
    assert spans == ["serve.cast_params"]
