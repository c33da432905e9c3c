"""Sharded deep-halo execution tests (DESIGN.md §12, docs/sharding.md).

Multi-device assertions run in a child process with 8 faked CPU devices
(`multidev_sharded_child.py`), per the dry-run isolation rule: the main
test process keeps its default 1-device view.  What runs here directly
is everything that needs no mesh (schedules, parsing, refusal helpers)
plus the 1-device-mesh transparent fallback.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_run_sharded_matches_run_on_faked_meshes():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests",
                                      "multidev_sharded_child.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"child failed:\n{r.stdout[-4000:]}\n{r.stderr[-4000:]}"
    assert "ALL-OK" in r.stdout
    # the full matrix ran: 10 specs x 2 meshes x 3 depths x 2 boundaries
    assert "equivalence: 120 configs OK" in r.stdout
    assert r.stdout.count("exchange-count") == 3
    assert r.stdout.count("refusal") == 3


def test_kernel_path_matches_references_on_four_cpu_devices():
    """3-D zero Dirichlet runs the sweep kernel on every shard
    (``multidev_sharded_kernel_child.py``): 2x2 and 1x2 meshes, remainder
    blocks, both kernel bodies, against ``kernels/ref`` and the
    benchmark's plain reference; ppermute count, op phases, counters."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests",
                                      "multidev_sharded_kernel_child.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"child failed:\n{r.stdout[-4000:]}\n{r.stderr[-4000:]}"
    assert "ALL-OK" in r.stdout
    assert r.stdout.count("agreement") == 4


# ------------------------------------------------- no-mesh-needed tests ----
def test_planned_exchange_rounds():
    from repro.api import planned_exchange_rounds
    assert planned_exchange_rounds(64, 4) == 16
    assert planned_exchange_rounds(9, 4) == 3     # 4, 4, remainder 1
    assert planned_exchange_rounds(3, 8) == 1     # one shallow block
    assert planned_exchange_rounds(5, 1) == 5     # t=1 IS per-step


def test_shard_extents_and_partition_spec_helpers():
    import numpy as np
    from jax.sharding import Mesh

    from repro.api.sharded import (shard_extents, sharded_partition_spec)

    class _Dev:                                   # no backend needed
        def __init__(self, i):
            self.id = i

    mesh = Mesh(np.array([[_Dev(0), _Dev(1)], [_Dev(2), _Dev(3)]]),
                ("shard0", "shard1"))
    assert shard_extents((8, 32, 5), mesh) == (4, 16, 5)
    assert sharded_partition_spec(3, mesh) == \
        __import__("jax").sharding.PartitionSpec("shard0", "shard1", None)


def test_validate_mesh_for_refusals_without_devices():
    import numpy as np
    from jax.sharding import Mesh

    from repro.api.sharded import validate_mesh_for
    from repro.core.stencil_spec import get

    class _Dev:
        def __init__(self, i):
            self.id = i

    mesh = Mesh(np.array([[_Dev(0), _Dev(1)], [_Dev(2), _Dev(3)]]),
                ("shard0", "shard1"))
    spec = get("j2d5pt")
    with pytest.raises(ValueError, match="not divisible"):
        validate_mesh_for(spec, (9, 32), mesh, 2, None)
    with pytest.raises(ValueError, match="Reduce t"):
        validate_mesh_for(spec, (8, 32), mesh, 8, None)
    validate_mesh_for(spec, (8, 32), mesh, 2, None)   # fits: no raise


def test_kernel_path_needs_twice_the_halo():
    """The sweep-kernel path (3-D zero Dirichlet) refuses a shard under
    2·t·radius on a dim of 2 shards, with the fix spelled out; other
    boundaries keep the one-halo rule, and a depth the planner chose is
    capped to what the shards hold."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.api import Boundary
    from repro.api.sharded import max_depth, uses_kernel, validate_mesh_for
    from repro.core.stencil_spec import get

    class _Dev:
        def __init__(self, i):
            self.id = i

    mesh = Mesh(np.array([[_Dev(0), _Dev(1)], [_Dev(2), _Dev(3)]]),
                ("shard0", "shard1"))
    spec = get("j3d7pt")
    assert uses_kernel(spec, None) and uses_kernel(spec, Boundary.dirichlet())
    assert not uses_kernel(spec, Boundary.periodic())
    assert not uses_kernel(get("j2d5pt"), None)
    with pytest.raises(ValueError, match=r"x 2 \(the sweep kernel's edge "
                       r"window\).*Reduce t to at most 3.*grow the domain "
                       r"to 16 cells on dim 0"):
        validate_mesh_for(spec, (14, 32, 8), mesh, 4, None)
    validate_mesh_for(spec, (16, 32, 8), mesh, 4, None)      # 2h fits
    validate_mesh_for(spec, (14, 32, 8), mesh, 4, Boundary.periodic())
    assert max_depth(spec, (14, 32, 8), (2, 2), None) == 3
    assert max_depth(spec, (14, 32, 8), (2, 2), Boundary.periodic()) == 7
    assert max_depth(spec, (14, 32, 8), (1, 1), None) is None


def test_parse_mesh_cli():
    import argparse

    from repro.launch.stencil_run import parse_mesh
    assert parse_mesh("8") == (8,)
    assert parse_mesh("2x4") == (2, 4)
    assert parse_mesh("2,4") == (2, 4)
    with pytest.raises(argparse.ArgumentTypeError):
        parse_mesh("2xbad")
    with pytest.raises(argparse.ArgumentTypeError):
        parse_mesh("0x4")


def test_single_device_mesh_falls_back_to_run():
    """mesh of total size 1: run_sharded is transparently .run — works on
    the plain 1-device test process."""
    import jax.numpy as jnp

    from repro.api import compile_stencil
    from repro.core.stencil_spec import get
    from repro.stencils.data import init_domain

    spec = get("j2d5pt")
    prog = compile_stencil(spec, (32, 48), t=2, mesh=1, interpret=True)
    single = compile_stencil(spec, (32, 48), t=2, interpret=True)
    x = init_domain(spec, (32, 48))
    assert prog.mesh is not None and prog.mesh.size == 1
    got = prog.run_sharded(x, 5)
    want = single.run(x, 5)
    assert float(jnp.abs(got - want).max()) == 0.0


def test_run_sharded_without_mesh_is_actionable():
    from repro.api import compile_stencil
    from repro.core.stencil_spec import get
    from repro.stencils.data import init_domain

    spec = get("j2d5pt")
    prog = compile_stencil(spec, (32, 48), t=2, interpret=True)
    x = init_domain(spec, (32, 48))
    with pytest.raises(ValueError, match="mesh-compiled"):
        prog.run_sharded(x, 4)


def test_mesh_programs_are_cached_separately():
    from repro.api import compile_stencil
    from repro.core.stencil_spec import get

    spec = get("j2d5pt")
    a = compile_stencil(spec, (32, 48), t=2, interpret=True)
    b = compile_stencil(spec, (32, 48), t=2, mesh=1, interpret=True)
    c = compile_stencil(spec, (32, 48), t=2, mesh=1, interpret=True)
    assert a is not b            # mesh is part of the program identity
    assert b is c                # same mesh: same memoized handle
