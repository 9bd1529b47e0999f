"""Training-substrate tests: optimizer, data determinism, checkpointing,
fault-tolerance runtime, end-to-end smoke training with restart."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import Checkpointer
from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM, make_source
from repro.optim.adamw import AdamWConfig, apply_updates, init_opt_state, schedule
from repro.runtime.fault_tolerance import StragglerDetector, TrainingRuntime


# --------------------------------------------------------------------------- #
# optimizer
# --------------------------------------------------------------------------- #


def test_adamw_reduces_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                      weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = init_opt_state(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, m = apply_updates(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_grad_clipping_bounds_update():
    cfg = AdamWConfig(lr=1.0, clip_norm=1.0, warmup_steps=0, total_steps=10)
    params = {"w": jnp.zeros(4)}
    state = init_opt_state(params)
    grads = {"w": jnp.full(4, 1e6)}
    _, _, metrics = apply_updates(params, grads, state, cfg)
    assert float(metrics["grad_norm"]) > 1e5     # raw norm reported


def test_schedule_warmup_and_cosine():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_frac=0.1)
    assert float(schedule(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(schedule(cfg, jnp.int32(100))) == pytest.approx(0.1)


# --------------------------------------------------------------------------- #
# data pipeline
# --------------------------------------------------------------------------- #


def test_data_deterministic_per_step():
    cfg = DataConfig(seed=3, global_batch=4, seq_len=32)
    s1 = SyntheticLM(cfg, vocab_size=101)
    s2 = SyntheticLM(cfg, vocab_size=101)
    np.testing.assert_array_equal(s1.batch(7)["tokens"], s2.batch(7)["tokens"])
    assert not np.array_equal(s1.batch(7)["tokens"], s1.batch(8)["tokens"])


def test_data_in_vocab_range():
    cfg = DataConfig(seed=0, global_batch=8, seq_len=64)
    src = SyntheticLM(cfg, vocab_size=50)
    toks = src.batch(0)["tokens"]
    assert toks.min() >= 0 and toks.max() < 50
    assert toks.shape == (8, 64)


def test_token_file_source(tmp_path):
    path = tmp_path / "toks.bin"
    arr = np.arange(10_000, dtype=np.int32) % 97
    arr.tofile(path)
    cfg = DataConfig(seed=1, global_batch=4, seq_len=16, source="file",
                     path=str(path))
    src = make_source(cfg, get_smoke_config("olmo-1b"))
    b = src.batch(3)["tokens"]
    assert b.shape == (4, 16)
    np.testing.assert_array_equal(src.batch(3)["tokens"], b)


# --------------------------------------------------------------------------- #
# checkpointing
# --------------------------------------------------------------------------- #


def make_tree(x=1.0):
    return {"a": jnp.full((4, 4), x), "b": {"c": jnp.arange(3) * 0 + int(x)}}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    tree = make_tree(2.0)
    ck.save(10, tree)
    assert ck.latest_step() == 10
    restored, step = ck.restore(10, jax.eval_shape(lambda: tree))
    assert step == 10
    np.testing.assert_allclose(restored["a"], np.full((4, 4), 2.0))


def test_checkpoint_async_and_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, make_tree(float(s)), blocking=False)
    ck.wait()
    ck._gc()
    assert ck.committed_steps() == [3, 4]


def test_checkpoint_atomic_commit_marker(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(5, make_tree())
    assert os.path.exists(tmp_path / "step_5.COMMITTED")
    # uncommitted junk is invisible
    os.makedirs(tmp_path / "step_99", exist_ok=True)
    assert ck.latest_step() == 5


def test_checkpoint_reshard_on_restore(tmp_path):
    """Elastic restore: apply different shardings than the writer used."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    ck = Checkpointer(str(tmp_path))
    tree = {"w": jnp.ones((8, 4))}
    ck.save(1, tree)
    mesh = jax.make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P(None, None))}
    restored, _ = ck.restore(1, jax.eval_shape(lambda: tree), shardings=sh)
    assert restored["w"].sharding == sh["w"]


# --------------------------------------------------------------------------- #
# runtime: straggler detection + restart
# --------------------------------------------------------------------------- #


def test_straggler_detector():
    d = StragglerDetector(alpha=0.5, threshold=2.0)
    assert not d.observe(0, 1.0)
    assert not d.observe(1, 1.1)
    assert d.observe(2, 10.0)
    assert d.slow_steps[0][0] == 2


def test_runtime_restart_is_exact(tmp_path):
    """Crash mid-run, restore, and land on the exact same final state."""
    ckpt_a = Checkpointer(str(tmp_path / "a"))
    ckpt_b = Checkpointer(str(tmp_path / "b"))

    def step_fn(carry, batch):
        new = jax.tree.map(lambda x: x + batch["tokens"].sum(), carry)
        return new, {"loss": jnp.zeros(())}

    def batch_fn(s):
        rng = np.random.default_rng(s)
        return {"tokens": jnp.asarray(rng.integers(0, 5, size=(2, 2)))}

    init = {"w": jnp.zeros(())}

    # uninterrupted reference
    rt = TrainingRuntime(ckpt_a, save_every=3, async_save=False)
    ref = rt.run(init, step_fn, batch_fn, 10)

    # crash at step 7, restart from checkpoint
    rt1 = TrainingRuntime(ckpt_b, save_every=3, async_save=False)
    with pytest.raises(RuntimeError):
        rt1.run(init, step_fn, batch_fn, 10, inject_fault_at=7)
    rt2 = TrainingRuntime(ckpt_b, save_every=3, async_save=False)
    restored = rt2.try_restore(jax.eval_shape(lambda: init))
    assert restored is not None
    carry, step = restored
    assert step == 6
    out = rt2.run(carry, step_fn, batch_fn, 10)
    np.testing.assert_allclose(out["w"], ref["w"])


# --------------------------------------------------------------------------- #
# end-to-end smoke training via the real driver
# --------------------------------------------------------------------------- #


def test_train_driver_loss_decreases(tmp_path):
    from repro.launch.train import main
    losses = main(["--arch", "olmo-1b", "--smoke", "--steps", "25",
                   "--batch", "8", "--seq", "64",
                   "--ckpt-dir", str(tmp_path)])
    assert losses[-1] < losses[0] - 0.3


@pytest.mark.parametrize("argv", [
    ["serve", "--arch", "olmo-1b", "--smoke", "--batch", "2",
     "--prompt-len", "8", "--gen", "4"],
    ["train", "--arch", "olmo-1b", "--smoke", "--steps", "2", "--batch", "2",
     "--seq", "16", "--ckpt-dir", "{ckpt}"],
])
def test_cli_entry_point_exits_zero(argv, tmp_path, monkeypatch):
    """``repro serve``/``repro train`` return results (tokens, losses), not
    statuses; the console script still exits 0 after them."""
    from repro import cli
    monkeypatch.setattr(sys, "argv", list(sys.argv))
    assert cli.main([a.format(ckpt=tmp_path) for a in argv]) == 0
