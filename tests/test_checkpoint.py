"""Checkpoint/restart + fault-tolerance tests."""
import os
import pathlib
import shutil

import jax
import numpy as np
import pytest

from repro import configs
from jax.sharding import AxisType
from repro.data.pipeline import TokenSource
from repro.training.checkpoint import CheckpointManager
from repro.training.trainer import Trainer, TrainerConfig


def _tree():
    return {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": np.asarray(3), "d": np.ones((4,), np.int32)}}


def test_roundtrip(tmp_path):
    cm = CheckpointManager(tmp_path, async_save=False)
    cm.save(3, _tree())
    out = cm.restore()
    np.testing.assert_array_equal(out["a"], _tree()["a"])
    np.testing.assert_array_equal(out["b"]["d"], _tree()["b"]["d"])
    assert cm.latest() == 3


def test_atomicity_no_partial_checkpoint(tmp_path):
    """A leftover .tmp dir is never picked up as a checkpoint."""
    cm = CheckpointManager(tmp_path, async_save=False)
    cm.save(1, _tree())
    # simulate a crash mid-save of step 2
    tmp = pathlib.Path(tmp_path) / "step_000000002.tmp"
    tmp.mkdir()
    (tmp / "leaves.npz").write_bytes(b"garbage")
    assert cm.latest() == 1
    out = cm.restore()
    assert out["b"]["c"][()] == 3


def test_retention_prunes(tmp_path):
    cm = CheckpointManager(tmp_path, keep_last=2, async_save=False)
    for s in (1, 2, 3, 4):
        cm.save(s, _tree())
    assert cm.all_steps() == [3, 4]


def test_async_save_waits(tmp_path):
    cm = CheckpointManager(tmp_path, async_save=True)
    cm.save(7, _tree())
    cm.wait()
    assert cm.latest() == 7


def test_trainer_crash_resume_end_to_end(tmp_path):
    cfg = configs.get("internvl2-1b", smoke=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
    ts = TokenSource(cfg.vocab_size, 16, 2)

    def batches():
        s = 0
        while True:
            b = ts.next_batch(s)
            b["frontend_embeds"] = np.zeros(
                (2, cfg.frontend_seq, cfg.d_model), np.float32)
            yield b
            s += 1

    tcfg = TrainerConfig(total_steps=8, ckpt_every=3, peak_lr=1e-3)
    tr = Trainer(cfg, mesh, tmp_path, tcfg)
    tr.init_or_restore()
    with pytest.raises(RuntimeError, match="injected"):
        tr.train(batches(), fail_at=5)
    # restart from scratch objects — must resume from step 3's checkpoint
    tr2 = Trainer(cfg, mesh, tmp_path, tcfg)
    tr2.init_or_restore()
    assert tr2.step == 3
    hist = tr2.train(batches())
    assert tr2.step == 8
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_straggler_detection(tmp_path):
    """Artificially slow step is recorded as a straggler."""
    import time
    cfg = configs.get("glm4-9b", smoke=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
    ts = TokenSource(cfg.vocab_size, 16, 2)
    tr = Trainer(cfg, mesh, tmp_path,
                 TrainerConfig(total_steps=6, ckpt_every=100,
                               straggler_factor=2.0))
    tr.init_or_restore()

    real_step = tr._jit_step
    calls = {"n": 0}

    def slow_step(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 6:            # injected straggler on step 6
            time.sleep(max(2.5 * 2.0 * (sum(tr.step_times) /
                                        max(len(tr.step_times), 1)), 0.2))
        return real_step(*a, **kw)

    tr._jit_step = slow_step

    def batches():
        s = 0
        while True:
            yield ts.next_batch(s)
            s += 1

    tr.train(batches())
    assert len(tr.step_times) == 6
    assert 5 in tr.straggler_steps, (tr.straggler_steps, tr.step_times)
