#!/usr/bin/env python3
"""Bring-up check: serve two models on one TPU through ``PagedEngine``
with the IAAT Router's kernels compiled for the chip.

    python chip_smoke.py [--seed 0]

Phase A serves mamba2-780m as published (48 layers, d_model 1536,
d_state 128, vocab 50280) under ``api.named_policy("auto")``; phase B
serves glm4-9b at its published widths with the depth cut to 4 of 40
layers, which runs the paged GQA attention path (32 query heads over 2
KV heads).  Weights are random, drawn from ``--seed``.  Each phase
checks that every request finished with real vocabulary ids, that the
compiled decode step holds a Pallas TPU kernel, and that every shape the
Router sent to Pallas agrees with an f32 ``jnp.dot`` on the chip.

The script runs in one process and starts none.  It exits non-zero,
without the final ``ok`` line, when JAX's backend is not a TPU, when it
does not sit in a checkout of the repository, or when any check fails.
The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

#: bf16 agreement of a Pallas-routed GEMM with an f32 ``jnp.dot`` on
#: O(1) outputs: |out - ref| <= TOL * (1 + |ref|).  The kernel accumulates
#: in f32, so the error is the final bf16 rounding (2**-9 relative).
TOL = 1e-2

PROMPT_LO, PROMPT_HI, MAX_NEW, MAX_LEN = 32, 160, 32, 256


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


#: Seconds the XLA backend spent compiling, summed from JAX's own
#: monitoring events once ``main`` registers the listener.
COMPILE_S = [0.0]


def _on_duration(name, secs, **_):
    if name == "/jax/core/compile/backend_compile_duration":
        COMPILE_S[0] += secs


def pallas_shapes(obs):
    """(M, K, N, dtype letter) of every GEMM shape the Router sent to
    Pallas, plus the Pallas / XLA / plan-overflow shape counts."""
    pallas, n_xla, n_overflow = [], 0, 0
    for (op, letter, _trans, dims), d in sorted(
            obs.ROUTES.decisions().items(), key=str):
        if d.use_pallas:
            check(op == "matmul", f"unexpected routed op {op} {dims}")
            pallas.append((math.prod(dims[:-2]), dims[-2], dims[-1], letter))
        else:
            n_xla += 1
            n_overflow += d.source == "plan_overflow"
    return pallas, n_xla, n_overflow


def check_pallas_shape(api, kernelgen, be, M, K, N, letter, key):
    """``api.matmul`` on the chip against an f32 ``jnp.dot``."""
    import jax
    import jax.numpy as jnp
    dtype = {**kernelgen.BLAS_DTYPES, **kernelgen.FRAMEWORK_DTYPES}[letter]
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (M, K), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (K, N), jnp.float32)
         / math.sqrt(K)).astype(dtype)
    out = jax.jit(lambda x, w: api.matmul(x, w, policy=be))(x, w)
    ref = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)
                        / (1.0 + jnp.abs(ref))))
    print(f"  pallas ({M},{K})@({K},{N}) {letter}: max scaled err "
          f"{err:.3e} (tol {TOL})")
    check(math.isfinite(err) and err <= TOL,
          f"pallas GEMM ({M},{K})@({K},{N}) off by {err}")


def serve_phase(name, cfg, *, slots, n_requests, seed, note=""):
    """Serve ``n_requests`` through PagedEngine the way launch/serve.py
    builds it, print what ran, and check it."""
    import jax
    import numpy as np

    from repro import api, obs, runtime
    from repro.core import kernelgen
    from repro.models.registry import build
    from repro.serve import PagedEngine, Request

    print(f"== phase {name}: {cfg.name} {note}".rstrip())
    print(f"  layers {cfg.n_layers} d_model {cfg.d_model} vocab {cfg.vocab} "
          f"slots {slots} requests {n_requests} max_new {MAX_NEW}")
    obs.ROUTES.reset()
    overflow0 = obs.counter("route.plan_overflow").value
    model = build(cfg)
    be = api.install(api.named_policy("auto"))
    check(not runtime.pallas_interpret(be.interpret),
          "Pallas kernels would run in interpret mode on this backend")
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    eng = PagedEngine(model, params, be, slots=slots, max_len=MAX_LEN,
                      temperature=0.0, seed=seed, block_size=16)
    rng = np.random.RandomState(seed)
    lens = np.linspace(PROMPT_LO, PROMPT_HI, n_requests).astype(int)
    for rid, n in enumerate(lens):
        eng.submit(Request(rid, rng.randint(0, cfg.vocab, n).astype(
            np.int32), max_new=MAX_NEW))
    c0, t0 = COMPILE_S[0], time.perf_counter()
    done = eng.run()
    wall, comp = time.perf_counter() - t0, COMPILE_S[0] - c0
    tokens = sum(len(v) for v in done.values())
    print(f"  served {len(done)}/{n_requests} requests, {tokens} tokens in "
          f"{wall} s wall, {comp} s of it in backend compiles")
    check(sorted(done) == list(range(n_requests)),
          f"finished {sorted(done)} of {n_requests} requests")
    for rid, out in sorted(done.items()):
        check(1 <= len(out) <= MAX_NEW, f"request {rid}: {len(out)} tokens")
        check(all(0 <= t < cfg.vocab for t in out),
              f"request {rid}: token outside the vocabulary: {out}")

    pallas, n_xla, n_overflow = pallas_shapes(obs)
    overflows = obs.counter("route.plan_overflow").value - overflow0
    print(f"  routed shapes: {len(pallas)} pallas, {n_xla} xla, "
          f"{n_overflow} plan-overflow (route.plan_overflow counter "
          f"+{overflows})")
    check(pallas, "no routed GEMM shape went to Pallas")

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    nmax = eng.cache.nmax
    step_args = jax.tree.map(spec, (
        eng.params, eng._cur, eng._ps, np.zeros((slots, nmax), np.int32),
        np.zeros((slots,), np.int32), np.zeros((slots,), bool), eng.key))
    hlo = eng._decode_fn.lower(*step_args).compile().as_text()
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"  compiled decode step: {n_kernels} tpu_custom_call")
    check(n_kernels >= 1, "the compiled decode step holds no Pallas kernel")

    key = jax.random.PRNGKey(seed + 1)
    for i, (M, K, N, letter) in enumerate(pallas):
        check_pallas_shape(api, kernelgen, be, M, K, N, letter,
                           jax.random.fold_in(key, i))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"  peak_bytes_in_use {peak}")
    del eng, params
    gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    print(f"jax {jax.__version__} devices {devices}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (backend {dev.platform!r}); this check "
             "never falls back to the CPU")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"{ROOT} is not a checkout of the repository (no src/repro)")
    sys.path.insert(0, SRC)
    from repro import configs, runtime
    print(f"compile cache {runtime.enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    # Phase A runs 4 slots: at 8 slots every mamba2-780m GEMM is above
    # the analytical threshold (cbrt(8*1536*3072) = 335 > 320), so the
    # decode step would hold no Pallas kernel to bring up.
    serve_phase("A", configs.get_config("mamba2-780m"), slots=4,
                n_requests=8, seed=args.seed)
    glm = configs.get_config("glm4-9b")
    serve_phase("B", dataclasses.replace(glm, n_layers=4), slots=4,
                n_requests=4, seed=args.seed,
                note=f"(depth cut to 4 of {glm.n_layers} layers; "
                     "published widths)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
