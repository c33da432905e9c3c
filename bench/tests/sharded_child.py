"""Child of ``test_bench_correctness``: one tiny run of a j3d7pt campaign
over a 2x2 mesh on four virtual CPU devices, sound or with the timed path
broken, printing ``correct=<bool>``.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python bench/tests/sharded_child.py no_exchange
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.tests import tiny  # noqa: E402


def main(mode: str) -> None:
    kw = {}
    if mode == "control":
        kw["control"] = True
    elif mode == "no_exchange":
        from repro.api import sharded

        def local_only(ext, dim, h, axis_name, n, boundary):
            pad = [(0, 0)] * ext.ndim
            pad[dim] = (h, h)
            return jnp.pad(ext, pad)

        sharded._exchange_sharded_axis = local_only
    elif mode == "state_unchanged":
        from repro.api.program import StencilProgram

        StencilProgram.run_sharded = lambda self, x, t: x
    elif mode != "sound":
        raise SystemExit(f"unknown mode {mode}")
    result = tiny.run("sharded", jax.devices()[:4], driver_kw=kw)
    print(f"correct={result['correct']} checks={result['checks']}")


if __name__ == "__main__":
    main(sys.argv[1])
