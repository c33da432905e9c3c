"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracle.

The Pallas kernels run in interpret mode on CPU (the TPU lowering path is
exercised structurally by the BlockSpecs; numerics are identical).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.stencil_spec import TABLE2, get
from repro.kernels import ops, ref
from repro.stencils.data import init_domain

SPECS_2D = [s for s in TABLE2.values() if s.ndim == 2]
SPECS_3D = [s for s in TABLE2.values() if s.ndim == 3]


def _check(got, want, dtype):
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("spec", SPECS_2D, ids=lambda s: s.name)
@pytest.mark.parametrize("shape", [(40, 56), (33, 129), (64, 64)])
@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ebisu2d_matches_reference(spec, shape, t, dtype):
    x = init_domain(spec, shape, dtype=dtype)
    want = ref.reference_unrolled(x.astype(jnp.float32), spec, t)
    got = ops.ebisu_stencil(x, spec, t, interpret=True)
    assert got.dtype == x.dtype
    assert got.shape == x.shape
    _check(got, want, dtype)


@pytest.mark.parametrize("spec", SPECS_2D, ids=lambda s: s.name)
def test_ebisu2d_scratch_mode(spec):
    x = init_domain(spec, (48, 72))
    t = 2
    want = ref.reference_unrolled(x, spec, t)
    got = ops.ebisu_stencil(x, spec, t, mode="scratch", interpret=True)
    _check(got, want, jnp.float32)


@pytest.mark.parametrize("spec", SPECS_2D, ids=lambda s: s.name)
def test_ebisu2d_deep_blocking(spec):
    """Depths comparable to the paper's Table 3 EBISU column."""
    from repro.core.stencil_spec import TABLE3_DEPTHS
    t = TABLE3_DEPTHS[spec.name]["ebisu"]
    x = init_domain(spec, (96, 80))
    want = ref.reference_unrolled(x, spec, t)
    got = ops.ebisu_stencil(x, spec, t, interpret=True)
    _check(got, want, jnp.float32)


@pytest.mark.parametrize("spec", SPECS_3D, ids=lambda s: s.name)
@pytest.mark.parametrize("shape", [(20, 9, 13), (24, 16, 16), (17, 7, 11)])
@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ebisu3d_matches_reference(spec, shape, t, dtype):
    x = init_domain(spec, shape, dtype=dtype)
    want = ref.reference_unrolled(x.astype(jnp.float32), spec, t)
    got = ops.ebisu_stencil(x, spec, t, interpret=True)
    assert got.dtype == x.dtype
    assert got.shape == x.shape
    _check(got, want, dtype)


@pytest.mark.parametrize("spec", SPECS_3D, ids=lambda s: s.name)
def test_ebisu3d_deep_blocking(spec):
    from repro.core.stencil_spec import TABLE3_DEPTHS
    t = TABLE3_DEPTHS[spec.name]["ebisu"]
    x = init_domain(spec, (2 * t * spec.radius + 8, 12, 12))
    want = ref.reference_unrolled(x, spec, t)
    got = ops.ebisu_stencil(x, spec, t, interpret=True)
    _check(got, want, jnp.float32)


def test_t_zero_and_one():
    spec = get("j2d5pt")
    x = init_domain(spec, (32, 32))
    got = ops.ebisu_stencil(x, spec, 1, interpret=True)
    _check(got, ref.stencil_step(x, spec), jnp.float32)


def test_non_divisible_domains():
    """Domains that don't divide the block sizes (padding correctness)."""
    spec = get("j3d7pt")
    x = init_domain(spec, (17, 7, 11))
    want = ref.reference_unrolled(x, spec, 2)
    got = ops.ebisu_stencil(x, spec, 2, interpret=True)
    _check(got, want, jnp.float32)


@pytest.mark.parametrize("spec", SPECS_2D, ids=lambda s: s.name)
@pytest.mark.parametrize("t", [1, 4])
def test_ebisu2d_streaming_mode(spec, t):
    """The paper's 2-D scheme: stream one dim through the circular
    multi-queue (lift_2d_to_3d) — no overlapped halo along the stream."""
    x = init_domain(spec, (72, 56))
    want = ref.reference_unrolled(x, spec, t)
    got = ops.ebisu_stencil(x, spec, t, mode="stream", interpret=True)
    _check(got, want, jnp.float32)


def test_stream_equals_strip_modes():
    """All three 2-D execution modes agree with each other exactly."""
    spec = get("j2d9pt")
    x = init_domain(spec, (64, 48))
    outs = [ops.ebisu_stencil(x, spec, 3, mode=m, interpret=True)
            for m in ("fused", "scratch", "stream")]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------ planner-chosen depths ----
# All nine Table-2 specs at the depth (and tile/batch) the §6 planner picks
# for v5e, on odd / non-multiple domains, through the full plan-wired path.

def _plan_for(spec):
    from repro.core import roofline as rl
    from repro.core.planner import plan
    return plan(spec, rl.TPU_V5E)


@pytest.mark.parametrize("mode", ["fused", "scratch"])
@pytest.mark.parametrize("spec", SPECS_2D, ids=lambda s: s.name)
def test_ebisu2d_planner_depth(spec, mode):
    p = _plan_for(spec)
    x = init_domain(spec, (97, 83))
    want = ref.reference_unrolled(x, spec, p.t)
    got = ops.ebisu_stencil(x, spec, p.t, plan=p, mode=mode, interpret=True)
    err = float(jnp.abs(got - want).max())
    assert err < 1e-4, (spec.name, p.t, mode, err)


@pytest.mark.parametrize("spec", SPECS_3D, ids=lambda s: s.name)
def test_ebisu3d_planner_depth(spec):
    p = _plan_for(spec)
    x = init_domain(spec, (2 * spec.halo(p.t) + 5, 9, 11))
    want = ref.reference_unrolled(x, spec, p.t)
    got = ops.ebisu_stencil(x, spec, p.t, plan=p, interpret=True)
    err = float(jnp.abs(got - want).max())
    assert err < 1e-4, (spec.name, p.t, err)


# ------------------------------------------------ XY device tiling ---------
# §6.3/§6.4 executed: the 3-D grid steps along y/x with halo-exact rim
# fetching, so planner-chosen in-plane tiles actually run.

@pytest.mark.parametrize("spec", SPECS_3D, ids=lambda s: s.name)
def test_ebisu3d_xy_tiled_matches_untiled(spec):
    """XY-tiled launch == untiled launch == oracle on a domain larger than
    one tile (corner rim views exercised by the box stencils).  Both
    launches go through the program front door with pinned plans — the
    sole dispatch path."""
    import dataclasses

    from repro.api import compile_stencil
    from repro.kernels.stencil3d import launch_geometry_3d

    t = 2
    halo = spec.halo(t)
    shape = (3 * halo + 5, 4 * halo + 3, 4 * halo + 6)
    x = init_domain(spec, shape)
    want = ref.reference_unrolled(x, spec, t)
    base = _plan_for(spec)

    def pinned(ty, tx):          # a tile >= the extent leaves the axis untiled
        return dataclasses.replace(base, t=t, halo=halo, lazy_batch=halo,
                                   block=(halo, ty, tx))

    untiled = compile_stencil(
        spec, shape, t=t, interpret=True,
        plan=pinned(shape[1], shape[2])).apply(x)
    tiled = compile_stencil(
        spec, shape, t=t, interpret=True,
        plan=pinned(2 * halo, 2 * halo)).apply(x)
    g = launch_geometry_3d(spec, t, shape, zc=halo, ty=2 * halo,
                           tx=2 * halo)
    assert g["grid"][1] > 1 and g["grid"][2] > 1, g
    _check(tiled, want, jnp.float32)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(untiled),
                               atol=1e-5, rtol=1e-5)


def test_ebisu3d_launch_geometry_honors_plan():
    """No planner output remains decorative: when the §6 planner tiles XY
    (the A100 scratchpad model does, on the paper domain), the launch grid
    the kernel resolves steps along y/x at exactly plan.block[1:]."""
    from repro.core import roofline as rl
    from repro.core.planner import plan

    spec = get("j3d7pt")
    p = plan(spec, rl.A100_FP64)
    assert p.block[1] < spec.domain[1] or p.block[2] < spec.domain[2], p
    g = ops.launch_geometry(spec, p.t, spec.domain, plan=p)
    assert g["grid"][1] > 1 or g["grid"][2] > 1, g
    assert g["block"][1:] == p.block[1:]


def test_ebisu3d_xy_tiling_plan_wired_end_to_end():
    """A plan whose block tiles XY flows through ops.ebisu_stencil into a
    tiled launch that still matches the oracle."""
    import dataclasses

    spec = get("j3d7pt")
    p = _plan_for(spec)
    halo = spec.halo(2)
    small = dataclasses.replace(p, t=2, block=(2 * halo, 2 * halo, 2 * halo),
                                halo=halo, lazy_batch=2 * halo)
    x = init_domain(spec, (10, 12, 14))
    g = ops.launch_geometry(spec, 2, x.shape, plan=small)
    assert g["grid"][1] > 1 and g["grid"][2] > 1, g
    want = ref.reference_unrolled(x, spec, 2)
    got = ops.ebisu_stencil(x, spec, 2, plan=small, interpret=True)
    _check(got, want, jnp.float32)


# ------------------------------------------------ aligned (Mosaic) body -----
# The body every chip launch runs, checked here through the interpreter:
# (8, 128)-aligned rims, ping-pong / ring scratch with zero row margins,
# roll shifts, fori_loop schedules.

@pytest.mark.parametrize("spec", SPECS_2D, ids=lambda s: s.name)
@pytest.mark.parametrize("shape,t,bh", [((50, 200), 3, 16),
                                        ((37, 128), 4, 40),
                                        ((70, 256), 6, 8)])
def test_ebisu2d_aligned_body_matches_reference(spec, shape, t, bh):
    from repro.kernels.stencil2d import ebisu2d, input_rows_per_strip

    x = init_domain(spec, shape)
    want = ref.reference_unrolled(x, spec, t)
    got = ebisu2d(x, spec, t, bh=bh, interpret=True, aligned=True)
    _check(got, want, jnp.float32)
    fetched, body = input_rows_per_strip(spec, t, bh, aligned=True)
    rim = -(-spec.halo(t) // 8) * 8
    assert body % rim == 0 and fetched == body + 2 * rim


@pytest.mark.parametrize("spec", SPECS_3D, ids=lambda s: s.name)
@pytest.mark.parametrize("shape,t,tile", [((20, 12, 40), 2, (None, None)),
                                          ((23, 30, 130), 3, (None, None)),
                                          ((19, 40, 300), 2, (8, 128))])
def test_ebisu3d_aligned_body_matches_reference(spec, shape, t, tile):
    from repro.kernels.stencil3d import ebisu3d, launch_geometry_3d

    x = init_domain(spec, shape)
    want = ref.reference_unrolled(x, spec, t)
    ty, tx = tile
    got = ebisu3d(x, spec, t, zc=4, ty=ty, tx=tx, interpret=True,
                  aligned=True)
    _check(got, want, jnp.float32)
    g = launch_geometry_3d(spec, t, shape, zc=4, ty=ty, tx=tx, aligned=True)
    assert g["tiled"][1:] == (ty is not None, tx is not None)
    _, yp, xp = g["padded"]
    _, by, bx = g["block"]
    assert yp % 8 == 0 and xp % 128 == 0
    assert (by % 8, bx % 128) == (0, 0) or ty is None
