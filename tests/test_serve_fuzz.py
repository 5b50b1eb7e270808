"""Seeded differential fuzz: random Poisson arrival traces through the
paged engine vs each request served alone, at temperature 0.

Each case draws a workload trace — Poisson inter-arrival gaps measured
in engine steps, mixed prompt lengths, mixed budgets — replays it into
a :class:`PagedEngine` whose pool is sized to force occasional
preemption, and demands token-identity with the single-request runs of
:func:`serve_oracle.serve_alone` for EVERY registry family.  Seeded, so a failure is a repro,
not a flake.  The full matrix is marked ``slow``; CI runs a small
instance (one ssm case) via ``-k``.
"""
import time

import jax
import numpy as np
import pytest

from repro import api, configs, obs
from repro.core.kernelgen import KernelSig
from repro.models import registry
from repro.models.common import XLA
from repro.serve import PagedEngine, Request
from repro.tune import classes as tune_classes, profile as profile_mod
from repro.tune.online import OnlineTuner
from repro.tune.profile import DeviceProfile, ProfileEntry
from repro.tune.timer import Measurement
from serve_oracle import serve_alone

pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)

# dense, MoE, VLM, ssm, hybrid
FUZZ_ARCHS = ("olmo-1b", "moonshot-v1-16b-a3b", "internvl2-2b",
              "mamba2-780m", "zamba2-7b")


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


@pytest.mark.parametrize("seed", (0, 1), ids=("s0", "s1"))
@pytest.mark.parametrize("arch", FUZZ_ARCHS)
def test_fuzz_poisson_trace_matches_serve_alone(get_model, arch, seed):
    cfg, model, params = get_model(arch)
    rng = np.random.RandomState(1000 * FUZZ_ARCHS.index(arch) + seed)
    n = 7
    prompts = [rng.randint(0, cfg.vocab,
                           int(rng.randint(2, 28))).astype(np.int32)
               for _ in range(n)]
    maxnew = [int(rng.randint(2, 10)) for _ in range(n)]
    arrivals = np.cumsum(rng.poisson(3, size=n))

    ref = serve_alone(model, params, prompts, maxnew)

    # pool of 7 usable blocks x 8 << 3 slots' worst case -> preemption
    # pressure; fits_ever still holds for every single request
    e = PagedEngine(model, params, XLA, slots=3, max_len=64, eos=-1,
                    block_size=8, chunk=8, num_blocks=8)
    t, nxt = 0, 0
    while nxt < n:
        while nxt < n and arrivals[nxt] <= t:
            e.submit(Request(nxt, prompts[nxt], max_new=maxnew[nxt]))
            nxt += 1
        e.step()
        t += 1
    assert e.run() == ref
    assert e.cache.blocks_in_use == 0
    assert e.state.bound == 0 and e.state.binds == e.state.releases


def _pref_profile(pallas_us, xla_us):
    """A profile with one measured entry for the 45^3 class, preferring
    whichever side was given the smaller timing."""
    m = lambda us: Measurement(us, us, us, 1)  # noqa: E731
    p = DeviceProfile(profile_mod.current_device_kind())
    p.record(tune_classes.size_class(45, 45, 45, "S", "NN"),
             ProfileEntry(KernelSig("S", "NN", 128, 128, 128),
                          m(pallas_us), m(xla_us), "online"))
    return p


def test_fuzz_online_swap_token_parity(get_model, tmp_path, monkeypatch):
    """PR-10 differential: live profile swaps mid-stream — from a real
    background OnlineTuner AND deterministic manual ``set_active_profile``
    calls between engine steps — must be temperature-0 token-identical
    to a swap-free run.  Routing lives at jit trace time, so a swap can
    flip what a NEW compilation picks but never the numerics of a
    compiled step: routing decisions may change, results may not (the
    decision flip is asserted too, so the test can't pass vacuously)."""
    monkeypatch.setenv(profile_mod.CACHE_ENV, str(tmp_path / "cache"))
    profile_mod.clear_active_profile()
    obs.reset()
    cfg, model, params = get_model("olmo-1b")
    rng = np.random.RandomState(42)
    n = 6
    prompts = [rng.randint(0, cfg.vocab,
                           int(rng.randint(2, 28))).astype(np.int32)
               for _ in range(n)]
    maxnew = [int(rng.randint(2, 10)) for _ in range(n)]
    arrivals = np.cumsum(rng.poisson(2, size=n))
    p1, p2 = _pref_profile(1.0, 9.0), _pref_profile(9.0, 1.0)

    def sweeper(targets, *, budget):
        # measured-entry double with the budgeted_sweep contract; keeps
        # the CI instance off the stopwatch while still driving real
        # merge + set_active_profile swaps from the tuner thread
        delta = DeviceProfile(profile_mod.current_device_kind())
        m = Measurement(1.0, 1.0, 1.0, 1)
        tuned = []
        for t in targets[: budget // 2]:
            e = ProfileEntry(KernelSig("S", "NN", 128, 128, 128), m,
                             Measurement(2.0, 2.0, 2.0, 1), "online")
            (delta.record_grouped if t.kind == "grouped"
             else delta.record)(t.sc, e)
            tuned.append(t)
        return delta, tuned, 2 * len(tuned)

    try:
        # reference: same trace, no tuner, no profile
        ref_e = PagedEngine(model, params, XLA, slots=3, max_len=64,
                            eos=-1, block_size=8, chunk=8, num_blocks=8)
        t, nxt = 0, 0
        while nxt < n:
            while nxt < n and arrivals[nxt] <= t:
                ref_e.submit(Request(nxt, prompts[nxt],
                                     max_new=maxnew[nxt]))
                nxt += 1
            ref_e.step()
            t += 1
        ref = ref_e.run()

        obs.reset()
        tuner = OnlineTuner(interval_s=0.02, budget=4, sweeper=sweeper)
        e = PagedEngine(model, params, XLA, slots=3, max_len=64, eos=-1,
                        block_size=8, chunk=8, num_blocks=8, tuner=tuner)
        assert tuner.start()
        t, nxt = 0, 0
        stopped_in_flight = False
        while nxt < n:
            while nxt < n and arrivals[nxt] <= t:
                e.submit(Request(nxt, prompts[nxt], max_new=maxnew[nxt]))
                nxt += 1
            if t == 2:
                profile_mod.set_active_profile(p1)
            elif t == 5:
                profile_mod.set_active_profile(p2)
            elif t == 7 and not stopped_in_flight:
                # shutdown with requests in flight must not deadlock —
                # join the thread, possibly mid-cycle, bounded wait
                time.sleep(0.05)            # let at least one cycle land
                assert tuner.stop(timeout=10.0)
                stopped_in_flight = True
            e.step()
            t += 1
        assert stopped_in_flight and not tuner.running
        out = e.run()      # engine restarts the tuner and stops it on drain
        assert not tuner.running

        assert out == ref                   # token identity, swaps and all
        assert e.cache.blocks_in_use == 0
        assert obs.counter("serve.engine_fallback").value == 0
        swaps = [ev for ev in obs.TRACE.snapshot()
                 if ev[1] == "PROFILE_SWAP"]
        assert len(swaps) >= 2              # the manual swaps at least
        assert tuner.cycles >= 1            # the background loop really ran

        # the non-vacuity half: tuned-mode routing DID change across the
        # same two profiles the stream survived
        pol = api.Policy(backend="tuned")
        profile_mod.set_active_profile(p1)
        d1 = api.route("gemm", (45, 45, 45), "S", "NN", policy=pol)
        profile_mod.set_active_profile(p2)
        d2 = api.route("gemm", (45, 45, 45), "S", "NN", policy=pol)
        assert d1.source == d2.source == "profile"
        assert d1.use_pallas and not d2.use_pallas
    finally:
        profile_mod.clear_active_profile()
        obs.reset()
