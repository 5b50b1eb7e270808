"""The timed path broken underneath: the rest of a run, with no look
for a chip, has to come out not correct.  Faults a served cell can have:
a decode step that returns its state unchanged, half of the slots of
the decode batch left out (the first half's rows take the second
half's tokens), and
a token altered where it is produced.  The sound run beside them comes out correct."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.conftest import add_files

SEED = 4242


def _state_unchanged(eng):
    orig = eng._decode_fn

    def decode(p, cur, ps, *rest):
        keep = jax.tree.map(jnp.copy, ps)
        nxt, _ps, key = orig(p, cur, ps, *rest)
        return nxt, keep, key
    eng._decode_fn = decode


def _token_altered(eng):
    orig = eng._decode_fn
    vocab = eng.model.cfg.vocab

    def decode(*args):
        nxt, ps, key = orig(*args)
        return (nxt + 1) % vocab, ps, key
    eng._decode_fn = decode


def _half_batch_left_out(eng):
    orig = eng._decode_fn

    def decode(*args):
        nxt, ps, key = orig(*args)
        half = nxt.shape[0] // 2
        return jnp.concatenate([nxt[half:], nxt[half:]]), ps, key
    eng._decode_fn = decode


FAULTS = {"sound": None, "state_unchanged": _state_unchanged,
          "half_batch_left_out": _half_batch_left_out,
          "token_altered": _token_altered}


@pytest.mark.parametrize("family", ["ssm", "dense"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(bench_copy, family, fault):
    cell = harness.load_cell(add_files(bench_copy, family), bench_copy)
    out = harness.run_cell(cell, SEED, 3.0, False, time.perf_counter(),
                           hook=FAULTS[fault], log=lambda *a, **k: None)
    gap = out["checks"]["max_logit_gap"]
    assert out["correct"] is (fault == "sound"), (fault, gap)
