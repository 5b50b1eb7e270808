"""Fixtures of the benchmark's own tests (CPU, tiny sizes).

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The repository's tier-1 run collects only ``tests/``, so these do not
change its count.  ``tiny_root`` is a temporary copy of the benchmark
(``BENCHMARK.json`` and ``bench/``) with one tiny configuration, mix and
cell of each family added as files, the way a later change adds them.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(REPO), str(REPO / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {
    "ssm": ("mamba2-780m", dict(
        n_layers=2, d_model=64, vocab=256,
        ssm={"d_state": 16, "head_dim": 8, "expand": 2, "d_conv": 4,
             "chunk": 16, "dt_min": 0.001, "dt_max": 0.1})),
    "dense": ("glm4-9b", dict(n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, head_dim=16, d_ff=160,
                              vocab=512)),
}

TINY_MIX = {
    "loop": "open",
    "engine": {"slots": 4, "max_len": 256, "policy": "auto"},
    "arrivals": {"dist": "gamma", "shape": 0.25},
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 1.0, "lo": 8,
               "hi": 200},
    "output": {"dist": "lognormal", "median": 24, "sigma": 0.5, "lo": 8,
               "hi": 48},
    "check": {"tokens": 160, "max_requests": 8},
}

#: A per-layer metric dropped in as a file: requests finished in the
#: window, read from the harness's own stamps.
TINY_METRIC = '''"""Requests of the window that finished (test metric)."""


def read(run):
    return sum(1 for r in run.window_reqs() if r.finish is not None)
'''


def add_files(root: pathlib.Path, family: str, limit: float = 0.5) -> str:
    """Add a tiny config, mix, cell and per-layer metric; returns the
    cell's name."""
    base, change = TINY[family]
    cfg = json.loads((REPO / "bench" / "configs" / f"{base}.json").read_text())
    cfg["config"].update(change)
    name = f"tiny-{family}"
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (root / "bench" / "mixes" / "tiny_chat.json").write_text(
        json.dumps(TINY_MIX))
    cell = f"{name}.tiny_chat"
    (root / "bench" / "workloads" / f"{cell}.json").write_text(json.dumps(
        {"rate_rps": 3.0, "limits": {"max_logit_gap": limit}}))
    (root / "bench" / "metrics" / "finished.tiny.py").write_text(TINY_METRIC)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "test",
                            "file": f"bench/configs/{name}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": name,
                              "traffic": "tiny_chat", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    metric = {"name": "finished.tiny", "unit": "requests", "better": "higher",
              "source": "host_clock", "layer": "scheduler",
              "moves": "tokens_per_s", "workloads": [cell]}
    if metric not in spec["per_layer"]:
        spec["per_layer"].append(metric)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


@pytest.fixture
def bench_copy(tmp_path):
    """A temporary copy of the benchmark's files."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


@pytest.fixture(autouse=True, scope="session")
def _no_compile_cache():
    """The harness switches JAX's persistent cache on; the tests keep
    their CPU programs out of the checkout's cache directory."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    yield
