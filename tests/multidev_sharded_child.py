"""Child process for ``StencilProgram.run_sharded`` multi-device tests.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
parent, ``tests/test_sharded.py``).  Asserts, on 1x8 and 2x4 faked CPU
meshes:

  * sharded == single-device ``.run`` (allclose at compute dtype) for all
    nine Table-2 specs plus a user-defined ``define_stencil`` spec, for
    t in {1, 2, 4} x {periodic, dirichlet(0)} (T = 2t+1 exercises the
    remainder block), plus reflect / dirichlet(v) / bf16 spot checks;
  * exactly ONE ppermute round per temporal block per sharded axis
    direction — not one per time step;
  * non-divisible domains and too-deep halos are refused with actionable
    errors.

Domain sizing: dim0 = 8*rad, dim1 = 32*rad (divisible by both meshes,
shard >= t*rad at every t tested), twice that for 3-D zero Dirichlet
(shard >= 2*t*rad, the sweep-kernel path's window), trailing 3-D dim
unsharded and small.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.api import (Boundary, compile_stencil, count_ppermutes,
                       define_stencil, planned_exchange_rounds)
from repro.api.sharded import build_sharded_runner, uses_kernel
from repro.core.stencil_spec import TABLE2
from repro.stencils.data import init_domain

MESHES = ((1, 8), (2, 4))
DEPTHS = (1, 2, 4)
BOUNDARIES = (Boundary.periodic(), Boundary.dirichlet(0.0))

CUSTOM = define_stencil(
    (((0, 0), 0.55), ((0, 1), 0.2), ((0, -1), 0.1),
     ((1, 0), 0.08), ((-1, 0), 0.04)), name="aniso5")  # unnormalized


def domain_for(spec, mesh, boundary=None):
    """Uniform shards on both meshes, shard >= 4*rad (the t=4 halo);
    shard >= 8*rad where the sweep-kernel path's window needs 2h at t=4
    (3-D zero Dirichlet)."""
    rad = spec.radius
    grow = 2 if uses_kernel(spec, boundary) else 1
    dims = [8 * rad * grow, 32 * rad * grow]
    if spec.ndim == 3:
        dims.append(max(2 * rad + 2, 8))
    return tuple(dims)


def check_equivalence():
    n = 0
    for spec in list(TABLE2.values()) + [CUSTOM]:
        for mesh in MESHES:
            for boundary in BOUNDARIES:
                shape = domain_for(spec, mesh, boundary)
                x = init_domain(spec, shape)
                for t in DEPTHS:
                    total = 2 * t + 1      # full, full, remainder
                    prog = compile_stencil(spec, shape, t=t, mesh=mesh,
                                           boundary=boundary,
                                           interpret=True)
                    single = compile_stencil(spec, shape, t=t,
                                             boundary=boundary,
                                             interpret=True)
                    got = prog.run_sharded(x, total)
                    want = single.run(x, total)
                    assert got.dtype == want.dtype == x.dtype
                    err = float(jnp.abs(got - want).max())
                    assert err < 2e-5, (spec.name, mesh, t, boundary, err)
                    n += 1
    print(f"equivalence: {n} configs OK "
          f"({len(TABLE2) + 1} specs x {len(MESHES)} meshes x "
          f"{len(DEPTHS)} depths x {len(BOUNDARIES)} boundaries)")


def check_exchange_counts():
    """One ppermute round per temporal block — NOT per time step."""
    for name, mesh, t, total in (("j2d5pt", (2, 4), 4, 9),
                                 ("j3d7pt", (1, 8), 2, 6),
                                 ("j2d9pt", (2, 4), 2, 5)):
        spec = TABLE2[name]
        shape = domain_for(spec, mesh, Boundary.periodic())
        prog = compile_stencil(spec, shape, t=t, mesh=mesh,
                               boundary=Boundary.periodic(), interpret=True)
        fn = build_sharded_runner(prog, total)
        x = init_domain(spec, shape)
        axes = sum(1 for nn in mesh if nn > 1)
        blocks = planned_exchange_rounds(total, t)
        got = count_ppermutes(fn, x)
        want = blocks * 2 * axes           # 2 directions per sharded axis
        per_step = total * 2 * axes        # the classic scheme's count
        assert got == want, (name, got, want)
        assert got < per_step or t == 1, (name, got, per_step)
        print(f"exchange-count {name} mesh={mesh} t={t} T={total}: "
              f"{got} ppermutes == {blocks} blocks x 2 x {axes} axes "
              f"(per-step scheme: {per_step})")


def check_spot_cases():
    # reflect: self-mirrored edge shards (mirror-symmetric taps)
    spec = TABLE2["j2d9pt"]
    shape = (16, 96)                       # shard >= h+1 on 1x8 at t=4
    x = init_domain(spec, shape)
    for boundary, t in ((Boundary.reflect(), 4),
                        (Boundary.dirichlet(0.7), 4)):   # s=1: any depth
        prog = compile_stencil(spec, shape, t=t, mesh=(1, 8),
                               boundary=boundary, interpret=True)
        single = compile_stencil(spec, shape, t=t, boundary=boundary,
                                 interpret=True)
        err = float(jnp.abs(prog.run_sharded(x, 2 * t + 1)
                            - single.run(x, 2 * t + 1)).max())
        assert err < 2e-5, (boundary, err)
        print(f"spot {boundary!r}: OK maxerr={err:.2e}")

    # unnormalized dirichlet(v): depth-1 blocks via the affine closure
    prog = compile_stencil(CUSTOM, (8, 32), t=1, mesh=(2, 4),
                           boundary=Boundary.dirichlet(0.3), interpret=True)
    single = compile_stencil(CUSTOM, (8, 32), t=1,
                             boundary=Boundary.dirichlet(0.3),
                             interpret=True)
    xa = init_domain(CUSTOM, (8, 32))
    err = float(jnp.abs(prog.run_sharded(xa, 3) - single.run(xa, 3)).max())
    assert err < 2e-5, err
    print(f"spot affine dirichlet(0.3) s!=1 t=1: OK maxerr={err:.2e}")

    # bf16 storage computes in f32 and lands back in bf16
    spec = TABLE2["j2d5pt"]
    prog = compile_stencil(spec, (8, 32), t=2, mesh=(2, 4),
                           dtype=jnp.bfloat16, interpret=True)
    xb = init_domain(spec, (8, 32), dtype=jnp.bfloat16)
    yb = prog.run_sharded(xb, 5)
    assert yb.dtype == jnp.bfloat16, yb.dtype
    print("spot bf16 storage: OK")

    # T=0 is the identity
    y0 = prog.run_sharded(xb, 0)
    assert y0 is xb
    print("spot T=0 identity: OK")


def check_refusals():
    spec = TABLE2["j2d5pt"]
    # non-divisible domain
    try:
        compile_stencil(spec, (17, 32), t=2, mesh=(2, 4), interpret=True)
        raise AssertionError("non-divisible domain not refused")
    except ValueError as e:
        msg = str(e)
        assert "divisible" in msg and "pad the domain" in msg, msg
        print("refusal non-divisible: OK")
    # halo deeper than one shard
    try:
        compile_stencil(spec, (8, 32), t=8, mesh=(2, 4), interpret=True)
        raise AssertionError("too-deep halo not refused")
    except ValueError as e:
        msg = str(e)
        assert "Reduce t" in msg and "one neighbor hop" in msg, msg
        print("refusal deep-halo: OK")
    # mesh with more axes than the domain has dims
    try:
        compile_stencil(spec, (8, 32), t=2, mesh=(2, 2, 2), interpret=True)
        raise AssertionError("over-ranked mesh not refused")
    except ValueError as e:
        assert "mesh has 3 axes" in str(e), e
        print("refusal mesh rank: OK")


def main():
    assert jax.device_count() == 8, jax.device_count()
    check_equivalence()
    check_exchange_counts()
    check_spot_cases()
    check_refusals()
    print("ALL-OK")


if __name__ == "__main__":
    main()
