"""Child process for the sweep-kernel path of ``run_sharded``.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4 (set by the
parent, ``tests/test_sharded.py``).  A 3-D zero-Dirichlet program runs
``kernels/stencil3d.ebisu3d_padded`` on every shard's edge-aligned window
(``api/sharded.py``); on 2x2 and 1x2 meshes, with and without a remainder
block, in the interpreter's body and in the Mosaic (aligned) body, it
asserts:

  * agreement with ``kernels/ref.reference`` and with the benchmark's
    plain reference ``bench/reference.py``, on a random field whose edge
    cells are not zero (a wrong edge window or a missed refresh fails);
  * ``count_ppermutes == planned_exchange_rounds x 2 x sharded axes``;
  * ``op_phases`` of the mesh program names ``stencil.exchange`` and
    ``stencil.sweep``, and one call adds its rounds and halo bytes to
    the counters.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import reference as bench_reference  # noqa: E402
from repro import telemetry  # noqa: E402
from repro.api import (compile_stencil, count_ppermutes,  # noqa: E402
                       planned_exchange_rounds)
from repro.api import sharded  # noqa: E402
from repro.api.sharded import (build_sharded_runner, halo_bytes,  # noqa: E402
                               uses_kernel)
from repro.core.stencil_spec import get  # noqa: E402
from repro.kernels.ref import reference  # noqa: E402

# (stencil, domain, mesh, t, T): the shards (16 x 24 and 24 x 24 cells on
# the sharded dims) hold the 2h cells an edge window takes from its
# neighbor.  A wrong cell next to a window's inner edge reaches the
# shard's own cells damped by a tap weight per cell it travels, so the
# shallow blocks are the ones that show a missed refresh.
CASES = (("j3d7pt", (32, 48, 128), (2, 2), 4, 12),
         ("j3d7pt", (32, 48, 128), (2, 2), 2, 9),     # blocks 2, 2, 2, 2, 1
         ("j3d7pt", (24, 48, 128), (1, 2), 1, 5),
         ("j3d13pt", (24, 48, 128), (1, 2), 3, 7))    # radius 2, 3, 3, 1
TOL = 1e-5


def field(shape):
    return jnp.asarray(np.random.default_rng(7).uniform(size=shape),
                       jnp.float32)


def check_agreement():
    for name, shape, mesh, t, total in CASES:
        spec = get(name)
        prog = compile_stencil(spec, shape, t=t, mesh=mesh, interpret=True)
        assert uses_kernel(prog.spec, prog.boundary)
        x = field(shape)
        oracle = reference(x, spec, total)
        plain = bench_reference.run(
            x, tuple((tuple(o), float(w)) for o, w in spec.taps), total)
        # the Mosaic body, which the chip runs, in the interpreter
        aligned = sharded.shard_mapped(
            prog, sharded._kernel_shards(prog, total, aligned=True))
        bodies = {"interpreter": prog.run_sharded(x, total),
                  "aligned": jax.jit(aligned)(x)}
        for body, y in bodies.items():
            for ref_name, want in (("ref", oracle), ("bench", plain)):
                err = float(jnp.abs(y - want).max())
                assert err < TOL, (name, shape, mesh, t, total, body,
                                   ref_name, err)
        print(f"agreement {name} {shape} mesh={mesh} t={t} T={total}: "
              f"interpreter and aligned bodies == ref and bench reference")


def check_exchange_counts():
    for name, shape, mesh, t, total in CASES:
        prog = compile_stencil(get(name), shape, t=t, mesh=mesh,
                               interpret=True)
        axes = sum(1 for n in mesh if n > 1)
        want = planned_exchange_rounds(total, t) * 2 * axes
        got = count_ppermutes(build_sharded_runner(prog, total),
                              field(shape))
        assert got == want, (name, mesh, total, got, want)
    print("exchange-count: ppermutes == rounds x 2 x axes in every case")


def check_phases_and_counters():
    name, shape, mesh, t, total = CASES[0]
    prog = compile_stencil(get(name), shape, t=t, mesh=mesh, interpret=True)
    phases = set(prog.op_phases(total).values())
    assert {"stencil.exchange", "stencil.sweep", "stencil.crop"} <= phases, \
        phases
    before = telemetry.snapshot()
    prog.run_sharded(field(shape), total).block_until_ready()
    after = telemetry.snapshot()
    rounds = after["exchange_rounds"] - before["exchange_rounds"]
    assert rounds == planned_exchange_rounds(total, t), rounds
    assert after["halo_bytes"] - before["halo_bytes"] == \
        halo_bytes(prog, total) > 0
    print(f"phases and counters: {sorted(phases)}, {rounds} rounds")


def main():
    assert jax.device_count() == 4, jax.device_count()
    check_agreement()
    check_exchange_counts()
    check_phases_and_counters()
    print("ALL-OK")


if __name__ == "__main__":
    main()
