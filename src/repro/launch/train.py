"""End-to-end training launcher.

Runs on anything from the CPU host mesh (smoke configs, examples, CI) to
the production pod mesh — same code path: config -> mesh -> rules ->
sharded state -> train loop with checkpointing, fault handling, straggler
monitoring, deterministic data.

    PYTHONPATH=src python -m repro.launch.train \
        --arch olmo-1b --smoke --steps 50 --batch 8 --seq 128 \
        --ckpt-dir /tmp/ckpt --ckpt-every 20
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import time

import jax
import jax.numpy as jnp

from repro import api, configs, obs, runtime
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import frontends
from repro.models.registry import build as build_model
from repro.parallel import rules as R
from repro.parallel.ctx import activation_axes, activation_sharding
from repro.train import checkpoint as ckpt_mod
from repro.train import data as data_mod
from repro.train import fault
from repro.train import loop as train_loop
from repro.train import optimizer as opt

log = logging.getLogger("repro.train")


def build_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--backend", default="xla",
                    choices=list(api.POLICY_NAMES))
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--inject-fault-at", type=int, default=-1,
                    help="simulate a node failure at this step (testing)")
    return ap.parse_args(argv)


def run(args) -> dict:
    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=args.multi_pod) \
        if args.production_mesh else make_host_mesh()
    rules = R.make_rules(cfg, mesh)
    # the single model-entry policy install: one frozen Policy for the
    # whole run, threaded to every layer (no per-projection re-config).
    # Training differentiates through the model, and the pallas
    # flash-attention/SSD kernels have no JVP — so the non-GEMM kernel
    # family is pinned to the XLA/ref paths while GEMM routing stays
    # input-aware (auto) or profile-refined (tuned): the routed GEMM
    # plan path carries a custom VJP.
    be = api.install(api.named_policy(args.backend).replace(kernels="xla"))
    tc = train_loop.TrainConfig(
        opt=opt.OptConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                          decay_steps=max(args.steps, 10)),
        accum_steps=args.accum)
    step_fn = train_loop.make_train_step(model, tc, be)
    state_specs = train_loop.train_state_specs(model)
    state_sh = rules.tree_shardings(state_specs)
    data = data_mod.SyntheticTokens(cfg.vocab, args.seq, args.batch,
                                    seed=args.seed)
    ckpt = ckpt_mod.Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    monitor = fault.StepMonitor()
    act_axes = activation_axes(cfg, mesh, R.batch_spec(mesh, args.batch))
    shape = configs.base.ShapeConfig("cli", args.seq, args.batch, "train")
    data_sh = R.data_shardings(cfg, shape, mesh, rules)
    metrics_out = {}

    def train_once(attempt: int) -> int:
        with mesh, activation_sharding(mesh, act_axes):
            start_step = 0
            state = None
            if ckpt and (args.resume or attempt > 0):
                latest = ckpt.latest_step()
                if latest is not None:
                    like = jax.eval_shape(
                        lambda: train_loop.init_train_state(
                            model, jax.random.PRNGKey(args.seed)))
                    state, extra = ckpt.restore(like, shardings=state_sh)
                    start_step = int(extra.get("data_step", latest))
                    log.info("restored step %d", start_step)
            if state is None:
                state = jax.jit(
                    lambda k: train_loop.init_train_state(model, k),
                    out_shardings=state_sh)(jax.random.PRNGKey(args.seed))
            jit_step = jax.jit(step_fn, in_shardings=(state_sh, None),
                               out_shardings=(state_sh, None),
                               donate_argnums=(0,))
            for step in range(start_step, args.steps):
                if step == args.inject_fault_at and attempt == 0:
                    raise fault.SimulatedFault(f"injected at step {step}")
                monitor.start()
                t0 = time.perf_counter()
                with obs.span("train.step"):
                    hb = data.batch(step, host=jax.process_index(),
                                    num_hosts=jax.process_count())
                    gb = data_mod.make_global_batch(hb, data_sh)
                    state, m = jit_step(state, gb)
                    m = {k: float(v) for k, v in m.items()}
                monitor.stop(step)
                train_loop.record_step(step, m,
                                       time.perf_counter() - t0)
                metrics_out.update(m, step=step)
                if step % args.log_every == 0 or step == args.steps - 1:
                    log.info("step %d loss %.4f gnorm %.3f lr %.2e",
                             step, m["loss"], m.get("grad_norm", 0),
                             m.get("lr", 0))
                if ckpt and args.ckpt_every and \
                        (step + 1) % args.ckpt_every == 0:
                    ckpt.save(step + 1, state,
                              extra={"data_step": step + 1}, async_=True)
            if ckpt:
                ckpt.save(args.steps, state,
                          extra={"data_step": args.steps})
                ckpt.wait()
            return args.steps

    final = fault.run_with_restarts(train_once,
                                    max_restarts=args.max_restarts)
    metrics_out["final_step"] = final
    metrics_out["monitor"] = monitor.summary()
    return metrics_out


def main() -> None:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    runtime.enable_compile_cache()
    out = run(build_args())
    print({k: v for k, v in out.items()})


if __name__ == "__main__":
    main()
