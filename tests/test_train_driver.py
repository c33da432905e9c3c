"""End-to-end trainer tests: loss goes down, checkpoints restart exactly,
and restarts reshard elastically onto a different mesh."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.launch.train import train

pytestmark = pytest.mark.slow


def test_train_loss_decreases(tmp_path):
    _, _, losses = train("mamba2-130m", steps=30, batch=8, seq=32,
                         reduced=True, ckpt_dir=str(tmp_path),
                         ckpt_every=10, lr=1e-2)
    assert losses[-1] < losses[0] - 0.2, losses[:3] + losses[-3:]


def test_crash_restart_resumes_identically(tmp_path):
    """train 20 steps straight == train 10, 'crash', resume 10 more."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    _, _, l_straight = train("h2o-danube-1.8b", steps=20, batch=4, seq=32,
                             reduced=True, ckpt_dir=d1, ckpt_every=10,
                             lr=1e-2, seed=3, schedule_steps=20)
    train("h2o-danube-1.8b", steps=10, batch=4, seq=32, reduced=True,
          ckpt_dir=d2, ckpt_every=10, lr=1e-2, seed=3, schedule_steps=20)
    _, _, l_resumed = train("h2o-danube-1.8b", steps=20, batch=4, seq=32,
                            reduced=True, ckpt_dir=d2, ckpt_every=10,
                            lr=1e-2, seed=3, resume="auto",
                            schedule_steps=20)
    # the deterministic (seed, step) pipeline makes the tail identical
    np.testing.assert_allclose(l_straight[10:], l_resumed,
                               rtol=2e-3, atol=2e-3)


def test_elastic_restart_new_mesh(tmp_path):
    """Checkpoint written on a 1-device mesh restores onto a 2x1 data mesh
    in a child process with 2 host devices (logical specs reshard freely)."""
    d = str(tmp_path)
    train("mamba2-130m", steps=6, batch=4, seq=32, reduced=True,
          ckpt_dir=d, ckpt_every=3, lr=1e-2, seed=1)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "from repro.launch.train import train\n"
        f"_,_,l = train('mamba2-130m', steps=9, batch=4, seq=32,"
        f" reduced=True, ckpt_dir={d!r}, ckpt_every=3, lr=1e-2, seed=1,"
        f" n_data=2, n_model=1)\n"
        "print('RESUMED-OK', l[-1])\n")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(root, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "resumed step 6" in r.stdout
    assert "RESUMED-OK" in r.stdout
