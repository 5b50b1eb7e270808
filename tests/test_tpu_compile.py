"""Compile the serving path's kernels and steps for a described TPU v5e.

Nothing runs: the TPU compiler, which ships with jaxlib, compiles for a
chip that is described and not attached, and refuses what Mosaic or the
device memory would refuse on the chip (interpret mode cannot show
either).  Every case pins ``interpret=False`` because this process's
backend is the CPU.  The topology is described inside a fixture, never
at import: only one process may load the TPU library, and the test
workers all import this file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api, configs
from repro.core import kernelgen, plan as plan_mod
from repro.kernels import grouped_gemm, iaat_gemm
from repro.models.registry import build
from repro.serve import paged_step_fns

HBM_BYTES = 16e9            # one TPU v5e chip
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a TPU compile written to the persistent cache cannot be read back
    # without a chip; keep it out of whatever cache the environment names
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _check(compiled, n_kernels=1):
    assert compiled.as_text().count(KERNEL) >= n_kernels
    assert _device_bytes(compiled) < HBM_BYTES


def _named_kernel(compiled, name):
    """The kernel's custom call is the HLO instruction named after its
    ``pallas_call``: the device trace shows that text."""
    pat = re.compile(rf"%{re.escape(name)}(\.\d+)? = [^\n]*custom-call\("
                     rf"[^\n]*{re.escape(KERNEL)}")
    assert pat.search(compiled.as_text()), name


def _kernels_named(compiled):
    """Every Pallas kernel of a serving step is a named IAAT GEMM."""
    calls = [ln for ln in compiled.as_text().splitlines() if KERNEL in ln]
    assert calls and all(re.search(r"%iaat_[\w.]+ = ", ln) for ln in calls)


# M < bm (16 for bf16) with a ragged K tail: Mosaic refused the packed
# K-mask select for all three before the mask selected in f32.
@pytest.mark.parametrize("M,K,N,dtype", [
    (4, 3072, 1536, jnp.bfloat16),    # mamba2-780m out_proj, 4 slots
    (4, 13696, 4096, jnp.bfloat16),   # glm4-9b decode wd
    (4, 1536, 6448, jnp.bfloat16),    # mamba2-780m in_proj, 4 slots
    (1, 3072, 1536, jnp.bfloat16),    # a single decoding slot
    (4, 3072, 1536, jnp.float32),
])
def test_routed_gemm_compiles(one_chip, M, K, N, dtype):
    pol = api.Policy(backend="pallas", interpret=False)
    x = jax.ShapeDtypeStruct((M, K), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((K, N), dtype, sharding=one_chip)
    compiled = jax.jit(lambda x, w: api.matmul(x, w, policy=pol)) \
        .lower(x, w).compile()
    _check(compiled)
    plan = plan_mod.build_plan(M, N, K, kernelgen.blas_letter(dtype), "NN",
                               pol.method)
    for region in plan.regions:
        _named_kernel(compiled, iaat_gemm.call_name(region.sig))


def test_grouped_gemm_short_rows_ragged_k_compiles(one_chip):
    x = jax.ShapeDtypeStruct((8, 4, 3000), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, 3000, 256), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, w: grouped_gemm.batched_gemm(
        x, w, interpret=False)).lower(x, w).compile()
    _check(compiled)
    bm, bn, bk = grouped_gemm.pick_blocks(4, 3000, 256, jnp.bfloat16)
    _named_kernel(compiled, f"iaat_batched_gemm_{bm}x{bn}x{bk}")


CHUNK = 32                  # PagedEngine's default prefill chunk


def _weight_shapes(template, served):
    """What a per-step cast of a weight would convert: each leaf that
    ``serving_params`` casts, whole and as the layer scan's slice."""
    shapes = set()
    for (path, p), s in zip(jax.tree_util.tree_flatten_with_path(template)[0],
                            jax.tree.leaves(served)):
        if s.dtype != p.dtype:
            shapes.add(p.shape)
            if path[0].key == "blocks":
                shapes.add(p.shape[1:])
    return {"x".join(map(str, sh)) for sh in shapes}


def _compile_paged_step(cfg, one_chip, slots, step="decode"):
    """Lower the engine's decode step or prefill chunk exactly as
    PagedEngine jits it, over the params it holds
    (``model.serving_params``); the lowered step converts no float32
    weight."""
    model = build(cfg)
    decode, prefill = paged_step_fns(model, api.named_policy(
        "auto", interpret=False))
    block_size, nmax = 16, 256 // 16              # max_len 256
    template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    served = jax.eval_shape(model.serving_params, template)
    params = _sds(served, one_chip)
    ps = _sds(jax.eval_shape(lambda: model.init_paged_state(
        1 + slots * nmax, block_size, slots, cfg.compute_dtype)), one_chip)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if step == "decode":
        lowered = jax.jit(decode, donate_argnums=(2,)).lower(
            params, arr((slots,)), ps, arr((slots, nmax)), arr((slots,)),
            arr((slots,), bool), arr((2,), jnp.uint32))
    else:
        lowered = jax.jit(prefill, donate_argnums=(2,)).lower(
            params, arr((1, CHUNK)), ps, arr((1, nmax)), arr((1,)), arr(()),
            arr(()), arr(()), arr(()))
    converted = set(re.findall(
        r"stablehlo\.convert %\S+ : \(tensor<([0-9x]*)xf32>\)",
        lowered.as_text()))
    weights = _weight_shapes(template, served)
    assert weights and not converted & weights
    return lowered.compile()


MAMBA2_SLOTS = 4


@pytest.fixture(scope="module")
def mamba2_decode(one_chip):
    """mamba2-780m's decode step at full width, all 48 layers, compiled
    once for the tests that read it."""
    cfg = configs.get_config("mamba2-780m")
    return cfg, _compile_paged_step(cfg, one_chip, slots=MAMBA2_SLOTS)


def test_mamba2_780m_paged_decode_step_compiles(mamba2_decode):
    """Full width, all 48 layers, 4 slots: out_proj (4,3072)@(3072,1536)
    routes to Pallas under ``auto`` and must lower to a TPU kernel."""
    _, compiled = mamba2_decode
    _check(compiled)
    _kernels_named(compiled)


def _hlo_type(x) -> str:
    """``f32[48,4,48,64,128]``: an array's type as the HLO text prints it."""
    short = {"float32": "f32", "bfloat16": "bf16"}
    return f"{short[jnp.dtype(x.dtype).name]}[{','.join(map(str, x.shape))}]"


def _tuple_types(s: str, i: int):
    """The element types of the parenthesised list that opens at
    ``s[i]``, each without its layout."""
    parts, depth, start = [], 0, i + 1
    for j in range(i, len(s)):
        depth += s[j] in "([{"
        depth -= s[j] in ")]}"
        if depth == 1 and s[j] == ",":
            parts.append(s[start:j])
            start = j + 1
        elif depth == 0:
            parts.append(s[start:j])
            break
    return [re.match(r"\s*(\w+\[[\d,]*\])", t).group(1) for t in parts]


def _state_aliases(text: str):
    """The (output, parameter) pairs of the entry computation's
    ``input_output_alias``, each given by its type."""
    head = re.sub(r"/\*[^*]*\*/", "", text.split("\n", 1)[0])
    key = "entry_computation_layout={"
    i = head.index(key) + len(key)
    ins = _tuple_types(head, i)
    outs = _tuple_types(head, head.index(")->(", i) + 3)
    return {(outs[int(o)], ins[int(p)]) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", head)}


def test_mamba2_780m_paged_decode_updates_state_in_place(mamba2_decode):
    """The decode step writes each layer's row of the donated slot state
    where it lies: no copy of the whole ``ssm`` or ``conv`` stack, and
    both stacks still come back in the donated buffers."""
    cfg, compiled = mamba2_decode
    text = compiled.as_text()
    ps = jax.eval_shape(lambda: build(cfg).init_paged_state(
        1, 16, MAMBA2_SLOTS, cfg.compute_dtype))
    aliases = _state_aliases(text)
    for stack in (ps.ssm, ps.conv):
        typ = _hlo_type(stack)
        assert stack.shape[:2] == (cfg.n_layers, MAMBA2_SLOTS)
        assert not re.search(rf"= {re.escape(typ)}(\{{[^}}]*\}})? copy\(",
                             text), typ
        assert (typ, typ) in aliases, typ


def test_glm4_9b_cut_paged_decode_step_compiles(one_chip):
    """Published widths, depth cut to 4 of 40 layers: the k/v
    projections (4,4096)@(4096,256) route to Pallas under ``auto``."""
    cfg = dataclasses.replace(configs.get_config("glm4-9b"), n_layers=4)
    compiled = _compile_paged_step(cfg, one_chip, slots=4)
    _check(compiled, n_kernels=2)
    _kernels_named(compiled)


def test_mamba2_780m_paged_prefill_step_compiles(one_chip):
    """Full width, all 48 layers: one 32-token prefill chunk over the
    bfloat16 serving weights."""
    cfg = configs.get_config("mamba2-780m")
    compiled = _compile_paged_step(cfg, one_chip, slots=4, step="prefill")
    assert _device_bytes(compiled) < HBM_BYTES


def test_glm4_9b_cut_paged_prefill_step_compiles(one_chip):
    """Published widths, depth cut to 4 of 40 layers: one 32-token
    prefill chunk over the bfloat16 serving weights."""
    cfg = dataclasses.replace(configs.get_config("glm4-9b"), n_layers=4)
    compiled = _compile_paged_step(cfg, one_chip, slots=4, step="prefill")
    assert _device_bytes(compiled) < HBM_BYTES
