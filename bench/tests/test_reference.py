"""Each plain reference against the program's own forward pass
(``repro.models``), at a small size, both in float32: the reference and
the program compute the same function of the same weights."""
import dataclasses

import jax
import numpy as np
import pytest

from bench import harness
from bench.reference import common
from bench.tests.conftest import add_files


@pytest.mark.parametrize("family", ["ssm", "dense"])
def test_reference_matches_program(bench_copy, family):
    from repro import api
    from repro.models.registry import build
    cell = harness.load_cell(add_files(bench_copy, family), bench_copy)
    cfg = dataclasses.replace(harness.model_config(cell), dtype="float32")
    model = build(cfg)
    shape = cell.shape(cfg.param_dtype)
    ref = harness.load_module(bench_copy / "bench" / "reference"
                              / f"{family}.py")
    key = jax.random.PRNGKey(3)
    params = common.make_params(ref.rules(shape),
                                jax.eval_shape(model.init, key), key)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 256), 0,
                              shape.vocab)
    prog, _ = model.forward_train(params, {"tokens": toks},
                                  api.named_policy("xla"))
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(params, shape, toks, common.dot_f32)
        want = common.dot_f32(h, ref.unembedding(params))
    prog = np.asarray(prog[..., :shape.vocab], np.float64)
    want = np.asarray(want[..., :shape.vocab], np.float64)
    assert np.max(np.abs(prog - want)) <= 1e-3 * max(1.0, np.abs(want).max())
