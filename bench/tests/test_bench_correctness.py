"""``correct`` is false for the control and for each fault a cell can have.

The harness runs here without its look for a chip, at a tiny size on the
CPU (Pallas kernels in the interpreter), with the timed path broken
underneath.  The control is the plain reference in the program's place,
computed in bfloat16 for a configuration that states float32.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench.tests import tiny
from repro.api.program import RUNNER_CACHE, StencilProgram

ONE_CHIP = ["j2d5pt.campaign", "j3d7pt.campaign", "service"]


def _unchanged(real):
    def run(self, x, *args):
        return x
    return run


def _altered(real):
    def run(self, x, *args):
        y = real(self, x, *args)
        return y.at[(0,) * y.ndim].add(1.0)
    return run


@pytest.fixture
def devices():
    RUNNER_CACHE.clear()
    yield jax.devices()[:1]
    RUNNER_CACHE.clear()


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(devices, name):
    result = tiny.run(name, devices)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(devices, name):
    # a short window checks an early call: on a tiny domain the zero
    # boundary drains the field within a few hundred steps, and a gap of
    # rounding shrinks with it
    result = tiny.run(name, devices, seconds=1e-3,
                      driver_kw={"control": True})
    assert not result["correct"], result["checks"]
    assert result["checks"]["max_abs_err"]["value"] > \
        result["checks"]["max_abs_err"]["limit"]


@pytest.mark.parametrize("fault", [_unchanged, _altered],
                         ids=["state_unchanged", "answer_altered"])
@pytest.mark.parametrize("name", ONE_CHIP)
def test_fault_is_not_correct(devices, monkeypatch, name, fault):
    for method in ("run", "run_batched"):
        real = getattr(StencilProgram, method)
        monkeypatch.setattr(StencilProgram, method, fault(real))
    result = tiny.run(name, devices)
    assert not result["correct"], result["checks"]


CHILD = Path(__file__).with_name("sharded_child.py")


@pytest.mark.parametrize("mode", ["sound", "control", "no_exchange",
                                  "state_unchanged"])
def test_sharded_cell_on_four_cpu_devices(mode):
    """A campaign over a 2x2 mesh (``run_sharded``) on four virtual CPU
    devices, in a child process (the device count is fixed when JAX
    starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(CHILD), mode], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    want = "correct=True" if mode == "sound" else "correct=False"
    assert want in proc.stdout, proc.stdout[-3000:]
