"""``sharded/`` bench family: deep-halo-per-block vs exchange-per-step.

What temporal blocking buys across a mesh is *fewer collective rounds*
at constant halo bytes (DESIGN.md §12): a ``T``-step run at block depth
``t`` performs ``ceil(T/t)`` ppermute rounds per sharded axis where the
classic ghost-exchange scheme performs ``T``.  Rows time
``run_sharded`` at the planned depth against the same program pinned to
``t=1`` (exchange every step) on a faked multi-device CPU mesh:

    sharded/<spec>-T<T>-mesh<MxN>  us_per_call
        derived: perstep_us|speedup|rounds=<blocked>/<perstep>|
                 halo_cells_per_round|note

``us_per_call`` is interpret-free jnp wall time (the per-shard compute
is the tap-engine chain), so the ratio — not the absolute time — is the
tracked quantity; rounds and halo cells are derived analytically from
the schedule and slab geometry.

Multi-device faking requires ``XLA_FLAGS=--xla_force_host_platform_
device_count`` *before* backend init, so ``rows()`` re-executes this
module as a child process (the same pattern as ``tests/multidev_*``)
and parses its CSV; run directly with ``--child`` inside such an
environment to see the rows without the wrapper.
"""
from __future__ import annotations

import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = (
    # name, shape, mesh, t, T
    ("j2d5pt", (64, 256), (2, 4), 6, 24),
    ("j3d7pt", (32, 32, 16), (2, 4), 4, 16),
)

N_DEVICES = 8


def halo_cells_per_round(shape, mesh, h: int) -> int:
    """Cells moved by one deep-halo exchange round (both directions, all
    sharded axes, the sequential-extension corner slabs included)."""
    ext = list(s // n for s, n in zip(shape, mesh)) + list(shape[len(mesh):])
    total = 0
    for d, n in enumerate(mesh):
        if n == 1:
            continue
        other = 1
        for k, e in enumerate(ext):
            if k != d:
                other *= e
        total += 2 * h * other * n          # per-shard slabs x shards
        ext[d] += 2 * h                     # later axes carry the corners
    return total


def _child_rows():
    import jax.numpy as jnp

    from benchmarks.common import time_pair
    from repro.api import compile_stencil, planned_exchange_rounds
    from repro.core.stencil_spec import get
    from repro.stencils.data import init_domain

    out = []
    for name, shape, mesh, t, total in CASES:
        spec = get(name)
        x = init_domain(spec, shape)
        blocked = compile_stencil(spec, shape, t=t, mesh=mesh,
                                  interpret=True)
        perstep = compile_stencil(spec, shape, t=1, mesh=mesh,
                                  interpret=True)
        yb = blocked.run_sharded(x, total)          # compile outside timing
        yp = perstep.run_sharded(x, total)
        assert float(jnp.abs(yb - yp).max()) < 1e-4, name
        us_blocked, us_perstep = time_pair(
            lambda: blocked.run_sharded(x, total),
            lambda: perstep.run_sharded(x, total), iters=5)
        r_blk = planned_exchange_rounds(total, t)
        mesh_s = "x".join(map(str, mesh))
        h = spec.halo(t)
        out.append((f"sharded/{name}-T{total}-mesh{mesh_s}", us_blocked,
                    f"perstep_us={us_perstep:.0f}|"
                    f"speedup={us_perstep / us_blocked:.2f}x|"
                    f"rounds={r_blk}/{total}|"
                    f"halo_cells_per_round={halo_cells_per_round(shape, mesh, h)}|"
                    f"note=deep-halo-per-block-vs-exchange-per-step"))
    return out


def rows():
    """Spawn the faked-multi-device child and parse its CSV rows.

    The child measures a faked CPU mesh by design, so it is pinned to the
    CPU: it must never reach for a chip that its parent may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"{env.get('XLA_FLAGS', '')} "
        f"--xla_force_host_platform_device_count={N_DEVICES}").strip()
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded", "--child"],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"sharded bench child failed:\n{r.stdout}\n"
                           f"{r.stderr}")
    out = []
    for line in r.stdout.splitlines():
        if line.startswith("sharded/"):
            name, us, derived = line.split(",", 2)
            out.append((name, float(us), derived))
    return out


if __name__ == "__main__":
    if "--child" in sys.argv:
        from benchmarks.common import emit
        emit(_child_rows())
    else:
        from benchmarks.common import emit
        emit(rows())
