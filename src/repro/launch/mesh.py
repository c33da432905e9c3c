"""Mesh construction for distributed/sharded execution.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's 512-placeholder-
device bootstrap to stay isolated from tests and benchmarks, and for the
``import repro.api`` backend-free gate (``scripts/tier1.sh``).

Two families of meshes live here:

  * the production LM meshes (``make_production_mesh``) — pod/data/model
    axes for the training/serving drivers;
  * stencil domain meshes (``make_stencil_mesh``) — one mesh axis per
    sharded *tensor* dimension, consumed by
    ``repro.api.compile_stencil(..., mesh=)`` / ``run_sharded``
    (DESIGN.md §12).  Axis ``shard<k>`` shards tensor dim ``k``.

Faked multi-device CPU (how every multi-device path in this repo is
tested and CI-smoked) — set **before** the first device query::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python ...

or from Python, before touching any device::

    from repro.launch.mesh import ensure_fake_devices
    ensure_fake_devices(8)

"""
from __future__ import annotations

import math
import os

import jax
from jax.sharding import AxisType, Mesh


def ensure_fake_devices(n: int) -> None:
    """Request >= ``n`` faked CPU devices (idempotent; must run before
    the JAX backend initializes — i.e. before any ``jax.devices()``
    call).

    Appends ``--xla_force_host_platform_device_count=n`` to
    ``XLA_FLAGS``; an existing device-count flag is kept when it already
    grants >= ``n`` devices and raised to ``n`` otherwise (other flags
    are preserved either way).

        from repro.launch.mesh import ensure_fake_devices
        ensure_fake_devices(4)            # then: import-time-lazy jax use
        assert len(jax.devices()) >= 4
    """
    import re

    n = int(n)
    flags = os.environ.get("XLA_FLAGS", "")
    pat = r"--xla_force_host_platform_device_count=(\d+)"
    m = re.search(pat, flags)
    if m:
        if int(m.group(1)) >= n:
            return
        flags = re.sub(pat,
                       f"--xla_force_host_platform_device_count={n}", flags)
    else:
        flags = f"{flags} --xla_force_host_platform_device_count={n}".strip()
    os.environ["XLA_FLAGS"] = flags


def _mk(shape, axes) -> Mesh:
    n = math.prod(shape)
    devs = jax.devices()
    assert len(devs) >= n, f"need {n} devices, have {len(devs)}"
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devs[:n])


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips) mesh.

    Axes: 'pod' (cross-pod data parallel, DCN-friendly — it only ever carries
    the once-per-step gradient all-reduce), 'data' (in-pod DP + ZeRO shards),
    'model' (tensor/expert parallel + stencil domain decomposition).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh for tests/examples."""
    return _mk(tuple(shape), tuple(axes))


def make_stencil_mesh(shape) -> Mesh:
    """A domain-decomposition mesh for ``compile_stencil(..., mesh=)``.

    Mesh axis ``k`` (named ``shard<k>``) shards tensor dimension ``k`` of
    the stencil domain; axes of size 1 leave their dimension unsharded.
    Devices are taken in ``jax.devices()`` order, so on a faked-CPU host
    this is deterministic.

        mesh = make_stencil_mesh((2, 4))       # 8 devices: dims 0 and 1
        prog = compile_stencil(spec, (256, 512), t=4, mesh=mesh)
        y = prog.run_sharded(x, 64)
    """
    shape = tuple(int(n) for n in shape)
    if not shape or any(n < 1 for n in shape):
        raise ValueError(f"mesh shape must be positive ints, got {shape}")
    return _mk(shape, tuple(f"shard{k}" for k in range(len(shape))))


def make_host_mesh(n_data: int = 1, n_model: int = 1) -> Mesh:
    """Smoke-test mesh over whatever devices the host actually has."""
    n = len(jax.devices())
    assert n_data * n_model <= n, (n_data, n_model, n)
    return _mk((n_data, n_model), ("data", "model"))
