"""The one traffic generator: a mix file of parameters -> a seeded plan.

Every seed gets the same set of sizes, and in an open loop the same
arrival schedule, drawn once from a fixed stream; ``--seed`` only orders
the sizes and picks the token ids.  So two seeds offer the same work at
the same moments, and what differs between them is which request comes
when.

Mix keys (``bench/mixes/<mix>.json``):

- ``loop``: ``"open"`` (requests arrive on a schedule, at the cell's
  ``rate_rps``) or ``"closed"`` (the harness keeps requests queued; the
  plan holds ``pool`` of them in order).
- ``arrivals`` (open loop): ``{"dist": "gamma", "shape": k}``; the gaps
  are scaled so that they fill the window exactly.
- ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "lo", "hi"}`` in tokens; the set is the distribution's quantiles at
  (i + 1/2) / n, clipped to [lo, hi].
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional

import numpy as np

#: Seed of the fixed stream the gap set is drawn from (never --seed).
SET_SEED = 20240117


@dataclasses.dataclass
class Plan:
    prompts: List[np.ndarray]          # int32 token ids, one per request
    max_new: np.ndarray                # output tokens per request
    arrivals: Optional[np.ndarray]     # seconds after window start; None
                                       # for a closed loop


def _lognormal_set(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    nd = statistics.NormalDist()
    mu = math.log(spec["median"])
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    v = np.exp(mu + spec["sigma"] * np.asarray(q))
    return np.clip(np.rint(v), spec["lo"], spec["hi"]).astype(np.int64)


def _gap_set(spec: dict, n: int, seconds: float) -> np.ndarray:
    if spec["dist"] != "gamma":
        raise ValueError(f"unknown arrival distribution {spec['dist']!r}")
    g = np.random.default_rng(SET_SEED).gamma(spec["shape"], 1.0, n)
    return g * (seconds / g.sum())


def n_requests(mix: dict, cell: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(cell["rate_rps"] * seconds)))
    return int(mix["pool"])


def make_plan(mix: dict, cell: dict, seed: int, seconds: float,
              vocab: int) -> Plan:
    """The requests of one run.  ``cell`` holds the workload's fixed
    parameters (``rate_rps`` for an open loop)."""
    n = n_requests(mix, cell, seconds)
    rng = np.random.default_rng(seed)
    prompt_len = rng.permutation(_lognormal_set(mix["prompt"], n))
    max_new = rng.permutation(_lognormal_set(mix["output"], n))
    max_len = mix["engine"]["max_len"]
    if int((prompt_len + max_new).max()) > max_len:
        raise ValueError(f"mix sizes exceed max_len {max_len}")
    arrivals = None
    if mix["loop"] == "open":
        gaps = _gap_set(mix["arrivals"], n, seconds)
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    elif mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    prompts = [rng.integers(0, vocab, int(s), dtype=np.int32)
               for s in prompt_len]
    return Plan(prompts, max_new, arrivals)
