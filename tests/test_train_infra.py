"""Integration tests: optimizer, checkpoint/restore, fault tolerance,
data determinism, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import registry
from repro.train import checkpoint as ck
from repro.train import data as data_mod
from repro.train import fault
from repro.train import loop as TL
from repro.train import optimizer as opt

KEY = jax.random.PRNGKey(0)


def test_adamw_reduces_loss_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init_opt_state(params)
    c = opt.OptConfig(peak_lr=0.2, warmup_steps=1, decay_steps=1000,
                      weight_decay=0.0)
    step = jnp.zeros((), jnp.int32)
    for i in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, m = opt.adamw_update(params, g, state, step + i, c)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_grad_clip():
    params = {"w": jnp.ones((4,))}
    st = opt.init_opt_state(params)
    g = {"w": jnp.full((4,), 1e6)}
    c = opt.OptConfig(clip_norm=1.0, warmup_steps=1)
    _, _, m = opt.adamw_update(params, g, st, jnp.zeros((), jnp.int32), c)
    assert float(m["grad_norm"]) > 1e5    # reported pre-clip


def test_schedule_warmup_then_decay():
    c = opt.OptConfig(peak_lr=1.0, warmup_steps=10, decay_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(opt.schedule(jnp.asarray(s), c)) for s in (0, 9, 50, 99)]
    assert lrs[0] < lrs[1] <= 1.0
    assert lrs[2] > lrs[3] >= 0.1 * 0.99


def test_checkpoint_roundtrip_and_gc(tmp_path):
    cfg = configs.get_smoke("olmo-1b")
    model = registry.build(cfg)
    state = TL.init_train_state(model, KEY)
    cp = ck.Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3):
        cp.save(s, state, extra={"data_step": s})
    assert cp.all_steps() == [2, 3]      # keep=2 GC'd step 1
    like = jax.eval_shape(lambda: TL.init_train_state(model, KEY))
    restored, extra = cp.restore(like)
    assert extra["data_step"] == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async(tmp_path):
    cp = ck.Checkpointer(str(tmp_path))
    state = {"a": jnp.arange(10)}
    cp.save(5, state, async_=True)
    cp.wait()
    restored, _ = cp.restore({"a": jnp.zeros(10, jnp.int32)})
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(10))


def test_checkpoint_atomicity(tmp_path):
    """A leftover .tmp dir is never treated as a checkpoint."""
    cp = ck.Checkpointer(str(tmp_path))
    os.makedirs(tmp_path / "step_00000009.tmp")
    cp.save(3, {"a": jnp.ones(3)})
    assert cp.latest_step() == 3


def test_data_determinism_and_host_sharding():
    d = data_mod.SyntheticTokens(vocab=100, seq_len=16, global_batch=8,
                                 seed=3)
    b1 = d.batch(7)
    b2 = d.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(d.batch(8)["tokens"], b1["tokens"])
    h0 = d.batch(7, host=0, num_hosts=2)
    h1 = d.batch(7, host=1, num_hosts=2)
    assert h0["tokens"].shape == (4, 16)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_memmap_dataset(tmp_path):
    arr = np.arange(10_000, dtype=np.int32) % 777
    path = tmp_path / "toks.bin"
    arr.tofile(path)
    d = data_mod.MemmapTokens(str(path), seq_len=16, global_batch=4)
    b = d.batch(0)
    assert b["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


def test_step_monitor_flags_stragglers():
    mon = fault.StepMonitor(z_thresh=2.0, warmup=3)
    import time
    for i in range(8):
        mon.start()
        time.sleep(0.001 if i != 6 else 0.08)
        st = mon.stop(i)
    assert any(s.straggler for s in mon.history)
    assert mon.summary()["stragglers"] >= 1


def test_run_with_restarts_retries():
    calls = []

    def train_once(attempt):
        calls.append(attempt)
        if attempt < 2:
            raise fault.SimulatedFault("boom")
        return 42

    assert fault.run_with_restarts(train_once, max_restarts=3) == 42
    assert calls == [0, 1, 2]


def test_training_recovers_after_fault(tmp_path):
    """End-to-end: fault at step k resumes from checkpoint, identical
    loss trajectory (deterministic data + exact checkpoint restore)."""
    from repro.launch.train import build_args, run
    args = build_args([
        "--arch", "olmo-1b", "--smoke", "--steps", "8", "--batch", "4",
        "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every", "4",
        "--inject-fault-at", "6", "--log-every", "100"])
    out = run(args)
    assert out["final_step"] == 8
    args2 = build_args([
        "--arch", "olmo-1b", "--smoke", "--steps", "8", "--batch", "4",
        "--seq", "32", "--log-every", "100"])
    out2 = run(args2)
    assert abs(out["loss"] - out2["loss"]) < 1e-4


def test_elastic_restore_resharding(tmp_path):
    """Checkpoint written under one 'mesh' restores under another (here:
    default device placement) — layout is mesh-independent."""
    cfg = configs.get_smoke("glm4-9b")
    model = registry.build(cfg)
    state = TL.init_train_state(model, KEY)
    cp = ck.Checkpointer(str(tmp_path))
    cp.save(1, state)
    like = jax.eval_shape(lambda: TL.init_train_state(model, KEY))
    restored, _ = cp.restore(like, shardings=None)
    assert jax.tree.structure(restored) == jax.tree.structure(state)
