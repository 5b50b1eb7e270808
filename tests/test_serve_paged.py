"""repro.serve.paged + sched + PagedEngine: block allocator properties,
scheduler state machine, and end-to-end parity with each request served
alone.

The parity oracle is :func:`serve_oracle.serve_alone`: the same engine at
``slots=1`` with a pool that never preempts, whose every request is in
turn held to the float32 ``forward_train`` reference.  A multi-slot run
must reproduce it token for token at temperature 0.
"""
import random

import jax
import numpy as np
import pytest

from repro import configs, obs
from repro.models import registry
from repro.models.common import XLA
from repro.serve import (BlockAllocator, CacheMap, OutOfBlocks,
                         PagedEngine, Request, Seq, SlotScheduler)
from repro.serve import sched
from serve_oracle import TOL, reference_gaps, reference_logits, serve_alone

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------
# Block allocator properties (pure host, no model).
# --------------------------------------------------------------------------

def test_allocator_unique_ids_and_exhaustion():
    a = BlockAllocator(8)                       # 7 usable; block 0 is null
    got = [a.alloc() for _ in range(7)]
    assert len(set(got)) == 7 and 0 not in got
    assert a.available == 0
    with pytest.raises(OutOfBlocks):
        a.alloc()


def test_allocator_double_free_and_null_free_rejected():
    a = BlockAllocator(4)
    b = a.alloc()
    a.free([b])
    with pytest.raises(ValueError):
        a.free([b])
    with pytest.raises(ValueError):
        a.free([0])


def test_allocator_churn_no_leak_no_alias():
    """Random alloc/free interleaving: held ids stay disjoint from the
    free list and held + available always equals capacity (no leak)."""
    a = BlockAllocator(16)
    rng = random.Random(0)
    held = []
    for _ in range(500):
        if held and (rng.random() < 0.5 or a.available == 0):
            a.free([held.pop(rng.randrange(len(held)))])
        else:
            b = a.alloc()
            assert b not in held, "allocator aliased a live block"
            held.append(b)
        assert len(held) + a.available == a.capacity
    a.free(held)
    assert a.available == a.capacity


def test_cache_map_grow_release_row():
    c = CacheMap(num_blocks=9, block_size=4, max_seq_len=16)
    c.ensure(7, 3)
    assert len(c.row(7)) == 4 and c.blocks_in_use == 1
    c.ensure(7, 9)                              # grow to 3 blocks
    row = c.row(7)
    assert c.blocks_in_use == 3 and (row[3] == 0)   # null-padded tail
    assert len(set(row[:3])) == 3
    c.release(7)
    assert c.blocks_in_use == 0 and c.allocator.available == 8
    assert c.fits_ever(16) and not c.fits_ever(17)


# --------------------------------------------------------------------------
# Scheduler state machine (host-only; CacheMap is pure host state).
# --------------------------------------------------------------------------

def _mk_sched(slots=2, num_blocks=9, block_size=4, max_seq=16):
    return SlotScheduler(CacheMap(num_blocks, block_size, max_seq), slots)


def _seq(rid, plen=3, max_new=4):
    return Seq(Request(rid, np.zeros(plen, np.int32), max_new=max_new))


def test_scheduler_fifo_admission_and_midflight_refill():
    s = _mk_sched(slots=2)
    for rid in range(4):
        s.submit(_seq(rid))
    admitted = s.admit()
    assert [q.rid for q in admitted] == [0, 1]      # FIFO into free slots
    assert s.admit() == []                          # slots full, queue waits
    s.finish(s.live[0])                             # mid-flight departure
    assert [q.rid for q in s.admit()] == [2]        # next in line, same slot
    assert sorted(s.live) == [1, 2]


def test_scheduler_finish_frees_blocks_and_slot():
    s = _mk_sched(slots=1)
    s.submit(_seq(5))
    (q,) = s.admit()
    s.cache.ensure(5, 9)
    assert s.cache.blocks_in_use == 3
    s.finish(q)
    assert s.cache.blocks_in_use == 0
    assert q.state == sched.DONE and s.slots[0] is None


def test_scheduler_preempt_requeues_front_and_frees():
    s = _mk_sched(slots=2)
    s.submit(_seq(0))
    s.submit(_seq(1))
    a, b = s.admit()
    s.cache.ensure(b.rid, 5)
    b.out = [7, 8]                              # generated prefix survives
    assert s.preempt_victim(a) is b             # youngest admitted loses
    s.preempt(b)
    assert s.cache.blocks_in_use == 0
    assert b.state == sched.QUEUED and b.pos == 0 and b.preemptions == 1
    assert b.out == [7, 8] and b.target[-2:] == [7, 8]
    s.submit(_seq(2))
    assert [q.rid for q in s.admit()] == [1]    # front of queue, before 2


def test_scheduler_rejects_never_fitting_request():
    s = _mk_sched(slots=1, num_blocks=3, block_size=4, max_seq=8)
    with pytest.raises(ValueError):
        s.submit(_seq(0, plen=6, max_new=8))    # 14 > 8-token pool


# --------------------------------------------------------------------------
# End-to-end parity with serving alone, over EVERY registry family.
# --------------------------------------------------------------------------

# one smoke arch per decoder-only family: dense, MoE, VLM, ssm, hybrid —
# the parity suite runs each so a family can't silently lose its paged
# path
PARITY_ARCHS = ("olmo-1b", "moonshot-v1-16b-a3b", "internvl2-2b",
                "mamba2-780m", "zamba2-7b")


@pytest.fixture(scope="module")
def get_model():
    """Module-cached (cfg, model, params) per arch, shared across the
    parametrized parity tests so each smoke model inits once."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = configs.get_smoke(arch)
            model = registry.build(cfg)
            cache[arch] = (cfg, model, model.init(KEY))
        return cache[arch]

    return get


@pytest.fixture(scope="module")
def smoke(get_model):
    return get_model("olmo-1b")


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_parity_mixed_lengths_mid_decode_admission(get_model, arch):
    """Token-identical to serving each request alone at temperature 0
    across mixed prompt lengths / budgets, with half the requests
    admitted mid-decode of the others."""
    cfg, model, params = get_model(arch)
    replays = obs.counter("serve.prefill_replay_chunks").value
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 3, 17, 2)]
    maxnew = [6, 5, 6, 3, 8]
    ref = serve_alone(model, params, prompts, maxnew)

    e = PagedEngine(model, params, XLA, slots=2, max_len=64, eos=-1,
                    block_size=8, chunk=8)
    for rid in range(2):
        e.submit(Request(rid, prompts[rid], max_new=maxnew[rid]))
    for _ in range(4):                          # both slots mid-decode
        e.step()
    for rid in range(2, 5):                     # admitted mid-flight
        e.submit(Request(rid, prompts[rid], max_new=maxnew[rid]))
    assert e.run() == ref
    assert e.cache.blocks_in_use == 0           # every eviction freed
    # nothing preempted, so no chunk replays decode rows
    assert obs.counter("serve.prefill_replay_chunks").value == replays


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_parity_under_preemption(get_model, arch):
    """A pool too small for both decoders forces preemption; recompute
    resume keeps the continuation token-identical — for the recurrent
    families this is the carry-rebuild path (prompt rows re-prefill with
    chunk numerics, replayed generated rows with decode numerics)."""
    cfg, model, params = get_model(arch)
    obs.reset()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab, 7).astype(np.int32)
               for _ in range(2)]
    ref = serve_alone(model, params, prompts, [10, 10])

    # capacity 3 blocks x 8 = 24 tokens; each request needs 2 blocks by
    # mid-decode, so demand hits 4 > 3 and the younger request cycles
    # through preempt -> re-queue -> recompute.
    e = PagedEngine(model, params, XLA, slots=2, max_len=24, eos=-1,
                    block_size=8, chunk=8, num_blocks=4)
    for rid, p in enumerate(prompts):
        e.submit(Request(rid, p, max_new=10))
    assert e.run() == ref
    assert obs.counter("serve.preemptions").value > 0
    # the resumed request's chunks past its prompt take the serial step
    assert obs.counter("serve.prefill_replay_chunks").value > 0
    assert e.cache.blocks_in_use == 0


def test_no_engine_fallback_for_registry_families():
    """Every decoder-only registry family builds with a paged serving
    path; the launcher's ``serve.engine_fallback`` counter (bumped only
    when a family misses the paged path) must stay 0."""
    obs.reset()
    for arch in PARITY_ARCHS:
        model = registry.build(configs.get_smoke(arch))
        assert model.paged_prefill is not None, arch
        assert model.paged_decode is not None, arch
        assert model.init_paged_state is not None, arch
    assert obs.counter("serve.engine_fallback").value == 0


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_parity_eos_eviction(get_model, arch):
    """EOS truncation matches serving alone and returns blocks."""
    cfg, model, params = get_model(arch)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab, n).astype(np.int32)
               for n in (4, 6)]
    free_run = serve_alone(model, params, prompts, [8, 8])
    eos = free_run[0][2]                        # a token that WILL appear
    ref = serve_alone(model, params, prompts, [8, 8], eos=eos)
    assert any(len(v) < 8 for v in ref.values())    # eviction exercised

    e = PagedEngine(model, params, XLA, slots=2, max_len=64, eos=eos,
                    block_size=8, chunk=8)
    for rid, p in enumerate(prompts):
        e.submit(Request(rid, p, max_new=8))
    assert e.run() == ref
    assert e.cache.blocks_in_use == 0


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_reference_check_rejects_a_wrong_token(get_model, arch):
    """Negative control of the reference check: one served token swapped
    for the id the reference ranks last fails it, by at least 4x TOL,
    while the tokens before it still pass."""
    cfg, model, params = get_model(arch)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, cfg.vocab, 9).astype(np.int32)
    out = serve_alone(model, params, [prompt], [6])[0]
    bad = list(out)
    bad[-1] = int(reference_logits(model, params, prompt, out)[-1].argmin())
    gaps = reference_gaps(model, params, prompt, bad)
    assert gaps[:-1].max() <= TOL
    assert gaps[-1] >= 4 * TOL, (arch, gaps[-1])


def test_chunked_prefill_does_not_starve_decode(smoke):
    """A short decoding request keeps emitting tokens while a long
    prompt prefills chunk-by-chunk next to it — the short one finishes
    BEFORE the long one produces its first token."""
    cfg, model, params = smoke
    rng = np.random.RandomState(4)
    short = rng.randint(0, cfg.vocab, 3).astype(np.int32)
    long = rng.randint(0, cfg.vocab, 48).astype(np.int32)

    e = PagedEngine(model, params, XLA, slots=2, max_len=64, eos=-1,
                    block_size=8, chunk=8)
    e.submit(Request(0, short, max_new=4))
    while not e.scheduler.decoding():           # short is decoding...
        e.step()
    e.submit(Request(1, long, max_new=2))       # ...long starts prefilling
    while 0 not in e.done:
        e.step()
    q = e.scheduler.live.get(1)
    assert q is not None and q.state == sched.PREFILL and not q.out
    done = e.run()
    assert sorted(done) == [0, 1] and len(done[1]) == 2
