"""Per-slot recurrent state: SlotStateStore invariants, scheduler
lockstep, masked-decode carry isolation, and property-style
slot-isolation runs.

The hazard these tests pin down: the paged engine multiplexes MANY
requests through a FIXED set of slot-state rows (conv carries + SSM
state), so any bookkeeping slip — a row not zero-reset on reuse, an
inactive row advanced by a masked decode step, a preempted request
resuming on a stale carry — silently leaks one request's recurrence
into another's tokens.  Every end-to-end check therefore compares
against single-request runs (:func:`serve_oracle.serve_alone`, one slot),
where no sharing exists by construction.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.models import registry
from repro.models.common import XLA
from repro.serve import (CacheMap, PagedEngine, Request, Seq, SlotScheduler,
                         SlotStateStore, paged_step_fns)
from serve_oracle import serve_alone

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def get_model():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = configs.get_smoke(arch)
            model = registry.build(cfg)
            cache[arch] = (cfg, model, model.init(KEY))
        return cache[arch]

    return get


# --------------------------------------------------------------------------
# SlotStateStore invariants (pure host).
# --------------------------------------------------------------------------

def test_store_bind_release_invariants():
    with pytest.raises(ValueError):
        SlotStateStore(0)
    s = SlotStateStore(2)
    s.bind(0, 10)
    with pytest.raises(ValueError):
        s.bind(0, 11)                   # occupied slot
    with pytest.raises(ValueError):
        s.bind(1, 10)                   # request already bound
    with pytest.raises(ValueError):
        s.bind(2, 12)                   # slot out of range
    s.bind(1, 11)
    assert s.bound == 2
    assert s.owner(0) == 10 and s.slot_of(11) == 1
    with pytest.raises(ValueError):
        s.release(99)                   # releases nothing it never held
    assert s.release(10) == 0
    assert s.owner(0) is None and s.slot_of(10) is None
    s.bind(0, 12)                       # freed slot immediately rebindable
    assert s.binds == 3 and s.releases == 1


def test_scheduler_keeps_store_in_lockstep():
    """bind on admit, release on finish AND on preempt — always next to
    the block-table release, never drifting from it."""
    store = SlotStateStore(2)
    s = SlotScheduler(CacheMap(9, 4, 16), 2, store)
    for rid in range(3):
        s.submit(Seq(Request(rid, np.zeros(3, np.int32), max_new=4)))
    a, b = s.admit()
    assert store.owner(a.slot) == a.rid and store.owner(b.slot) == b.rid
    s.cache.ensure(b.rid, 5)
    bslot = b.slot
    s.preempt(b)
    assert store.owner(bslot) is None
    assert store.slot_of(b.rid) is None and s.cache.blocks_in_use == 0
    (c,) = s.admit()                    # preempted seq re-admits, rebinds
    assert c.rid == b.rid and store.slot_of(c.rid) == c.slot
    s.finish(a)
    assert store.slot_of(a.rid) is None
    assert store.binds == 3 and store.releases == 2
    assert store.bound == 1             # only the resumed seq remains


# --------------------------------------------------------------------------
# Masked decode: inactive slot rows are bitwise frozen (device).
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["mamba2-780m", "zamba2-7b"])
def test_masked_decode_freezes_inactive_carries(get_model, arch):
    """A decode step with a slot masked inactive must leave that slot's
    conv/ssm rows bitwise unchanged and touch no pool block but the
    null sink (block 0), while each active slot's rows equal the carry
    of a one-slot decode of the same tokens — mamba2 exercises the
    recurrent rows alone, zamba2 the rows and the shared-attention pool
    in one model."""
    cfg, model, params = get_model(arch)
    slots = 3
    ps = model.init_paged_state(1 + slots, 8, slots)
    null = jnp.zeros((slots, 4), jnp.int32)         # all-null tables
    own = null.at[:, 0].set(jnp.arange(1, slots + 1, dtype=jnp.int32))
    pos = jnp.zeros((slots,), jnp.int32)
    toks = {"tokens": jnp.arange(1, slots + 1, dtype=jnp.int32)[:, None]}
    nxt = {"tokens": toks["tokens"] + slots}

    def same(a, b):
        return bool(jnp.all(a == b))

    def alone(s, *steps):
        """Slot s's tokens of ``steps``, decoded in a one-slot state."""
        one = model.init_paged_state(2, 8, 1)
        table = jnp.array([[1, 0, 0, 0]], jnp.int32)
        for t, step in enumerate(steps):
            _, one = model.paged_decode(
                params, {"tokens": step["tokens"][s:s + 1]}, one, table,
                jnp.array([t], jnp.int32), jnp.ones((1,), bool), XLA)
        return one

    # one all-active step so the carries are non-zero (a frozen zero
    # row proves nothing); each slot's rows are its one-slot run's
    _, ps1 = model.paged_decode(params, toks, ps, own, pos,
                                jnp.ones((slots,), bool), XLA)
    assert bool(jnp.any(ps1.conv != 0)) and bool(jnp.any(ps1.ssm != 0))
    for s in range(slots):
        one = alone(s, toks)
        assert same(ps1.conv[:, s], one.conv[:, 0])
        assert same(ps1.ssm[:, s], one.ssm[:, 0])

    # all-inactive step: different tokens, nothing may move
    _, ps2 = model.paged_decode(params, nxt, ps1, null, pos + 1,
                                jnp.zeros((slots,), bool), XLA)
    assert same(ps2.conv, ps1.conv) and same(ps2.ssm, ps1.ssm)
    if ps.shared_k is not None:         # writes landed in the null sink only
        assert same(ps2.shared_k[:, 1:], ps1.shared_k[:, 1:])
        assert same(ps2.shared_v[:, 1:], ps1.shared_v[:, 1:])

    # mixed step: only the active slot's rows advance, as alone they do
    act = jnp.array([False, True, False])
    _, ps3 = model.paged_decode(params, nxt, ps2, null.at[1].set(own[1]),
                                pos + 1, act, XLA)
    for s in (0, 2):
        assert same(ps3.conv[:, s], ps2.conv[:, s])
        assert same(ps3.ssm[:, s], ps2.ssm[:, s])
    assert bool(jnp.any(ps3.ssm[:, 1] != ps2.ssm[:, 1]))
    one = alone(1, toks, nxt)
    assert same(ps3.conv[:, 1], one.conv[:, 0])
    assert same(ps3.ssm[:, 1], one.ssm[:, 0])


# --------------------------------------------------------------------------
# Property-style slot isolation (end-to-end vs single-request runs).
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch,seed", [("mamba2-780m", 0),
                                       ("mamba2-780m", 1),
                                       ("zamba2-7b", 0)])
def test_slot_isolation_random_interleaving(get_model, arch, seed):
    """Random interleavings of admit / decode / budget-evict / preempt
    (pool sized to exhaust) over shared slots: every request's tokens
    must equal its single-request reference run — any cross-slot carry
    leak or stale-row reuse shows up as a token flip."""
    cfg, model, params = get_model(arch)
    rng = np.random.RandomState(seed)
    n = 6
    prompts = [rng.randint(0, cfg.vocab,
                           int(rng.randint(2, 20))).astype(np.int32)
               for _ in range(n)]
    maxnew = [int(rng.randint(2, 9)) for _ in range(n)]
    ref = serve_alone(model, params, prompts, maxnew)

    e = PagedEngine(model, params, XLA, slots=2, max_len=64, eos=-1,
                    block_size=8, chunk=8, num_blocks=6)
    e.submit(Request(0, prompts[0], max_new=maxnew[0]))
    for rid in range(1, n):             # admissions land mid-flight
        for _ in range(int(rng.randint(0, 5))):
            e.step()
        e.submit(Request(rid, prompts[rid], max_new=maxnew[rid]))
    assert e.run() == ref
    assert e.state.bound == 0 and e.state.binds == e.state.releases
    assert e.cache.blocks_in_use == 0


def test_slot_isolation_eos_evict_and_reuse(get_model):
    """EOS-evicted slots hand their state row to the next request; the
    successor must start from a zero carry, not the evictee's."""
    cfg, model, params = get_model("mamba2-780m")
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab, p).astype(np.int32)
               for p in (4, 6, 9, 5)]
    free = serve_alone(model, params, prompts, [8, 8, 8, 8])
    eos = free[0][2]                    # a token that WILL appear
    ref = serve_alone(model, params, prompts, [8, 8, 8, 8], eos=eos)
    assert any(len(v) < 8 for v in ref.values())    # eviction exercised

    e = PagedEngine(model, params, XLA, slots=2, max_len=64, eos=eos,
                    block_size=8, chunk=8)
    for rid, p in enumerate(prompts):
        e.submit(Request(rid, p, max_new=8))
    assert e.run() == ref
    assert e.state.bound == 0 and e.state.binds == 4


def test_exhaustion_resume_rebuilds_carry(get_model):
    """Block exhaustion preempts a decoding SSM request (carry row
    released with the blocks); recompute-resume re-prefills
    prompt+generated from a zero row and the continuation is
    token-identical to the never-preempted reference."""
    cfg, model, params = get_model("mamba2-780m")
    obs.reset()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab, 7).astype(np.int32)
               for _ in range(2)]
    ref = serve_alone(model, params, prompts, [10, 10])

    e = PagedEngine(model, params, XLA, slots=2, max_len=24, eos=-1,
                    block_size=8, chunk=8, num_blocks=4)
    for rid, p in enumerate(prompts):
        e.submit(Request(rid, p, max_new=10))
    assert e.run() == ref
    assert obs.counter("serve.preemptions").value > 0
    assert e.state.binds > 2            # at least one resume re-bound
    assert e.state.bound == 0 and e.cache.blocks_in_use == 0


@pytest.mark.parametrize("arch", ["mamba2-780m", "zamba2-7b"])
def test_replay_chunks_rebuild_carry_bitwise(get_model, arch):
    """The SSM state a recompute-resume rebuilds is the unpreempted
    run's, bit for bit: a prompt prefilled in one chunk then decoded
    token by token leaves the same SSM rows as prefilling prompt and
    generated tokens together, where the chunk rows past the prompt
    take the serial decode step and the prompt rows the chunked form.
    The conv carry is held bitwise after the chunk that holds the
    prompt's end; after a chunk of replayed rows alone it can differ in
    the last bit of a bfloat16 input (a GEMM over 8 rows against one),
    which the token parity tests absorb."""
    cfg, model, params = get_model(arch)
    decode, prefill = (jax.jit(f) for f in paged_step_fns(model, XLA))
    rng = np.random.RandomState(7)
    C, n_prompt = 8, 7
    toks = rng.randint(0, cfg.vocab, 2 * C).astype(np.int32)
    table = jnp.array([[1, 2, 3]], jnp.int32)
    key = jax.random.PRNGKey(0)
    i32 = np.int32

    def chunk(ps, p0, seg):
        row = np.zeros((1, C), np.int32)
        row[0, :seg] = toks[p0:p0 + seg]
        _, ps = prefill(params, jnp.asarray(row), ps, table,
                        jnp.array([p0], jnp.int32), i32(0), i32(seg),
                        i32(n_prompt), i32(seg - 1))
        return ps

    # served without preemption: the prompt's chunk, then decode steps
    run = chunk(model.init_paged_state(4, 8, 1), 0, n_prompt)
    states = {}
    for t in range(n_prompt, 2 * C):
        _, run, key = decode(params, jnp.asarray(toks[t:t + 1]), run, table,
                             jnp.array([t], jnp.int32),
                             jnp.ones((1,), bool), key)
        states[t + 1] = run
    # resumed: prompt and generated tokens re-prefilled from position 0
    resumed = chunk(model.init_paged_state(4, 8, 1), 0, C)
    for p0, ps in ((C, resumed), (2 * C, chunk(resumed, C, C))):
        assert bool(jnp.all(ps.ssm == states[p0].ssm)), (arch, p0)
    assert bool(jnp.all(resumed.conv == states[C].conv)), arch
