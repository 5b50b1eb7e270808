"""The generator: one seed gives one plan; every seed offers the same
sizes and gaps in another order; arrivals fill the window."""
import numpy as np

from bench import traffic

MIX = {"loop": "open", "engine": {"max_len": 1280},
       "arrivals": {"dist": "gamma", "shape": 0.25},
       "prompt": {"dist": "lognormal", "median": 128, "sigma": 1.0,
                  "lo": 16, "hi": 1024},
       "output": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                  "lo": 8, "hi": 256}}
CELL = {"rate_rps": 2.5}
BIG = 2**33 + 5


def test_same_seed_same_plan():
    a = traffic.make_plan(MIX, CELL, BIG, 40, 50280)
    b = traffic.make_plan(MIX, CELL, BIG, 40, 50280)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert np.array_equal(a.arrivals, b.arrivals)


def test_seeds_share_sizes_and_schedule():
    a = traffic.make_plan(MIX, CELL, 1, 40, 50280)
    b = traffic.make_plan(MIX, CELL, BIG, 40, 50280)
    assert len(a.prompts) == len(b.prompts) == 100
    assert sorted(map(len, a.prompts)) == sorted(map(len, b.prompts))
    assert sorted(a.max_new) == sorted(b.max_new)
    assert np.array_equal(a.arrivals, b.arrivals)
    assert not np.array_equal(a.max_new, b.max_new)
    assert np.isclose(np.diff(a.arrivals, append=40).sum(), 40)
    assert a.arrivals[0] == 0 and a.arrivals[-1] < 40
    lens = np.array(sorted(map(len, a.prompts)))
    assert lens.min() >= 16 and lens.max() <= 1024
    assert abs(np.median(lens) - 128) <= 2


def test_closed_loop_pool():
    mix = dict(MIX, loop="closed", pool=64)
    p = traffic.make_plan(mix, {}, 3, 40, 100)
    assert p.arrivals is None and len(p.prompts) == 64
    assert all(int(t.max()) < 100 for t in p.prompts)
