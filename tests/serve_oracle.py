"""The serving tests' two oracles.

**Scheduling invariance** (:func:`serve_alone`).  A request's tokens must
not depend on what else the engine serves beside it.  The oracle serves
each request alone: ``PagedEngine(slots=1)`` with a pool that holds the
longest request, so it never preempts.  A multi-slot, interleaved,
preempting or evicting run must give every request exactly these tokens,
bit for bit, at temperature 0.

**Model correctness** (:func:`reference_gaps`).  The served tokens must
also be the model's.  The reference is ``Model.forward_train``, the
full-sequence forward that training uses, with the config at
``dtype="float32"`` and the same weights.  It runs once, teacher-forced,
over prompt + served tokens.  At each served position the number read is
how far the served token's reference logit lies below the reference's
best logit over the real vocabulary.  Serving is greedy, so a sound
engine serves the reference's best token or one within bfloat16 rounding
of it.  :func:`serve_alone` holds every request it serves to
:data:`TOL`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np

from repro import obs
from repro.models import registry
from repro.models.common import XLA
from repro.serve import PagedEngine, Request

#: Longest prompt + budget a test request may have: the engines' and the
#: reference's padded length.
MAX_LEN = 64

#: Widest gap a served token may have, measured with the tokens of the
#: retired wave engine at one slot.  Over the parity cases of
#: ``test_serve_paged.py``, ``test_serve_state.py`` and
#: ``test_serve_fuzz.py`` (133 requests, five families) the widest gap
#: was 0.0633 (zamba2-7b, fuzz seed 1); per family 0.0115 (olmo-1b),
#: 0.0003 (moonshot-v1-16b-a3b), 0.0216 (internvl2-2b), 0.0281
#: (mamba2-780m).  The EOS case run in every family adds a wider one:
#: 0.158 in moonshot-v1-16b-a3b, where the first served token is the
#: reference's second best, 0.158 below a top three that lie within
#: 0.19 of each other.  TOL rounds that up.  The narrowest best-to-worst
#: spread of a position's reference logits was 4.65, so a token the
#: reference ranks last misses TOL more than 20 times over.
TOL = 0.2


def serve_alone(model, params, prompts, maxnew, eos=-1):
    """Every request served alone, in order, by one ``PagedEngine`` with
    one slot; returns ``{rid: tokens}`` with rid the index in
    ``prompts``.  Each request is held to the reference
    (:func:`reference_gaps` within :data:`TOL`)."""
    before = obs.counter("serve.preemptions").value
    e = PagedEngine(model, params, XLA, slots=1, max_len=MAX_LEN, eos=eos,
                    block_size=8, chunk=8)
    for rid, (p, mn) in enumerate(zip(prompts, maxnew)):
        e.submit(Request(rid, p, max_new=mn))
    out = e.run()
    assert obs.counter("serve.preemptions").value == before
    for rid, p in enumerate(prompts):
        gaps = reference_gaps(model, params, p, out[rid])
        assert gaps.max() <= TOL, (model.cfg.name, rid, gaps.max())
    return out


@functools.lru_cache(maxsize=None)
def _reference(cfg):
    ref = registry.build(dataclasses.replace(cfg, dtype="float32"))
    return jax.jit(
        lambda p, toks: ref.forward_train(p, {"tokens": toks}, XLA)[0][0])


def reference_logits(model, params, prompt, served) -> np.ndarray:
    """The reference's logits over the real vocabulary, one row per
    served token: row j is what the reference predicts after ``prompt``
    and ``served[:j]``.  The sequence is padded to :data:`MAX_LEN`; the
    model is causal, so the padding moves no row that is read."""
    full = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    toks = np.zeros((1, MAX_LEN), np.int32)
    toks[0, :len(full)] = full
    lg = np.asarray(_reference(model.cfg)(params, toks), np.float64)
    return lg[len(prompt) - 1 + np.arange(len(served)), :model.cfg.vocab]


def reference_gaps(model, params, prompt, served) -> np.ndarray:
    """Per served token: the reference's best logit at its position less
    the reference logit of the token served there."""
    lg = reference_logits(model, params, prompt, served)
    return lg.max(1) - lg[np.arange(len(served)), np.asarray(served)]
