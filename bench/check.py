"""How ``correct`` is decided: served tokens against the plain reference.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the one with the most served tokens, is run
through the family's float32 reference (``bench/reference/<family>.py``)
as prompt + served tokens.  At each served position the number read is
how far the served token's reference logit lies below the reference's
best logit over the real vocabulary.  Serving is greedy, so a sound
program serves the reference's best token, or one within rounding of it;
the run's number is the widest such gap.

The control (``bench/control.py``) reads the same gap for the token that
the reference computed with float8 matmul operands puts first, at the
same positions.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

#: Rows of hidden state turned into logits at a time.
ROWS = 128


def pick(finished: Dict[int, Tuple[np.ndarray, List[int]]], seed: int,
         tokens: int, max_requests: int) -> List[int]:
    """Request ids to compare: the one with the most served tokens, then
    others in an order drawn from the seed, until ``tokens`` served
    tokens or ``max_requests`` requests are in."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r][1]), -r))
    rest = [r for r in rids if r != longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    out, n = [longest], len(finished[longest][1])
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(finished[rest[i]][1])
    return out


class Reference:
    """The family's reference at one padded length and batch, compiled
    once per run."""

    def __init__(self, ref_mod, shape, params, length: int, batch: int):
        import jax
        from bench.reference import common
        self.ref, self.shape, self.params = ref_mod, shape, params
        self.length, self.batch = length, batch
        self.vocab = shape.vocab

        def hidden(p, toks, mode):
            with jax.default_matmul_precision("highest"):
                return ref_mod.hidden(p, shape, toks, common.DOTS[mode])

        def logits(p, h, mode):
            with jax.default_matmul_precision("highest"):
                lg = common.DOTS[mode](h, ref_mod.unembedding(p))
            return lg[:, :self.vocab]

        self._hidden = jax.jit(hidden, static_argnums=2)
        self._logits = jax.jit(logits, static_argnums=2)

    def gaps(self, seqs: Sequence[Tuple[np.ndarray, List[int]]],
             control: bool = False):
        """Per served token: (gap of the served token, gap of the token
        the control puts first or None)."""
        import jax.numpy as jnp
        toks = np.zeros((self.batch, self.length), np.int32)
        rows, served = [], []
        if len(seqs) > self.batch:
            raise ValueError(f"{len(seqs)} requests > batch {self.batch}")
        for b, (prompt, out) in enumerate(seqs):
            full = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
            if len(full) > self.length:
                raise ValueError(f"sequence of {len(full)} > {self.length}")
            toks[b, :len(full)] = full
            n = len(prompt)
            rows += [b * self.length + n - 1 + j for j in range(len(out))]
            served += list(out)
        modes = ("f32", "fp8") if control else ("f32",)
        hid = {m: self._hidden(self.params, jnp.asarray(toks), m)
               .reshape(self.batch * self.length, -1) for m in modes}
        gap, gap_c = [], []
        for i in range(0, len(rows), ROWS):
            r = np.zeros(ROWS, np.int32)
            blk = rows[i:i + ROWS]
            r[:len(blk)] = blk
            lg = np.asarray(self._logits(self.params, hid["f32"][r], "f32"))
            lg = lg[:len(blk)].astype(np.float64)
            best = lg.max(axis=1)
            tok = np.asarray(served[i:i + ROWS])
            gap += list(best - lg[np.arange(len(blk)), tok])
            if control:
                lc = np.asarray(self._logits(self.params, hid["fp8"][r], "fp8"))
                tc = lc[:len(blk)].argmax(axis=1)
                gap_c += list(best - lg[np.arange(len(blk)), tc])
        return np.asarray(gap), (np.asarray(gap_c) if control else None)
