"""Distributed EBISU: deep-halo exchange + temporal blocking across chips.

The paper amortizes *device-wide synchronization* over ``t`` fused time steps
(§4.1/§5.2.2).  Across a TPU pod the analogous synchronization is the halo
exchange: this module exchanges a ``t_block·rad``-deep halo **once per
t_block steps** (`ppermute` over ICI), which

  * divides the number of collective launches (and their latency / sync cost)
    by ``t_block`` — the distributed version of Eq 11's ``n`` reduction;
  * keeps total halo *bytes* constant (depth × 1/frequency), so the roofline
    collective-bytes term is flat while the collective-*count* term drops;
  * pays ``V_SMtile``-style redundant compute on the halo (Eq 8/9) — the same
    trade the paper makes inside a device, lifted to the pod level.

Domain decomposition is N-dimensional: each sharded tensor dim maps to a mesh
axis.  Halo exchange is sequential per axis on the progressively extended
array, so box-stencil corners arrive via two hops (standard corner trick).

Per-shard inner compute is the fused jnp blocked step with *global-coordinate*
masking (axis_index-dependent), which keeps zero-Dirichlet semantics exact at
the true domain edges while interior shard seams are healed by the halo.  The
single-device Pallas kernels remain the on-chip realization of the same
schedule; wiring them inside shard_map needs a per-shard scalar-prefetch
origin operand (see DESIGN.md §8 — stretch item).
"""
from __future__ import annotations

import functools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.stencil_spec import StencilSpec
from repro.kernels.ref import stencil_step


def _axis_size(mesh, ax) -> int:
    if isinstance(ax, str):
        return mesh.shape[ax]
    import math
    return math.prod(mesh.shape[a] for a in ax)


def _axis_index(ax):
    """Flattened index over a (possibly tuple) mesh axis, major-to-minor."""
    if isinstance(ax, str):
        return jax.lax.axis_index(ax)
    idx = jax.lax.axis_index(ax[0])
    for a in ax[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _exchange_one_axis(local: jnp.ndarray, dim: int, h: int, axis_name,
                       n: int, *, periodic: bool = False):
    """Extend ``local`` by h-deep halos along ``dim`` from mesh neighbors.

    Open chain (default): shards at the ends receive zeros (ppermute
    drops sourceless outputs), which is exactly the zero-extension the
    global Dirichlet boundary needs.  ``periodic=True`` closes the chain
    into a ring — shard 0's low halo is shard n−1's last rows, realizing
    the torus seam with the same one-round exchange.  ``axis_name`` may
    be a tuple of mesh axes (flattened ordering).
    """
    if n == 1:
        pad = [(0, 0)] * local.ndim
        pad[dim] = (h, h)
        mode = dict(mode="wrap") if periodic else {}
        return jnp.pad(local, pad, **mode)
    idx_lo = [slice(None)] * local.ndim
    idx_lo[dim] = slice(0, h)
    idx_hi = [slice(None)] * local.ndim
    idx_hi[dim] = slice(local.shape[dim] - h, local.shape[dim])
    last = n if periodic else n - 1    # ring closes the (n-1, 0) hop
    # shard i's top halo <- shard i-1's last rows (data flows "down": i->i+1)
    from_prev = jax.lax.ppermute(local[tuple(idx_hi)], axis_name,
                                 [(i, (i + 1) % n) for i in range(last)])
    # shard i's bottom halo <- shard i+1's first rows
    from_next = jax.lax.ppermute(local[tuple(idx_lo)], axis_name,
                                 [((i + 1) % n, i) for i in range(last)])
    return jnp.concatenate([from_prev, local, from_next], axis=dim)


def _blocked_steps(ext: jnp.ndarray, spec: StencilSpec, t_block: int,
                   origins: Mapping[int, jnp.ndarray],
                   global_shape: Sequence[int]) -> jnp.ndarray:
    """t_block fused steps on the extended shard, re-masking every step so
    cells outside the *global* domain stay zero (exact Dirichlet semantics).
    Unsharded dims are zero-extended by stencil_step's padding, which is
    already exact for them."""
    mask = None
    for dim, origin in origins.items():
        ids = jnp.arange(ext.shape[dim]) + origin
        ok = (ids >= 0) & (ids < global_shape[dim])
        shape = [1] * ext.ndim
        shape[dim] = ext.shape[dim]
        ok = ok.reshape(shape)
        mask = ok if mask is None else mask & ok
    for _ in range(t_block):
        ext = stencil_step(ext, spec)
        if mask is not None:
            ext = jnp.where(mask, ext, 0.0)
    return ext


def make_distributed_stencil(spec: StencilSpec, mesh: Mesh,
                             dim_to_axis: Mapping[int, str],
                             global_shape: Sequence[int],
                             t_total: int, t_block: int,
                             inner: str = "jnp"):
    """Build a jit-able ``fn(x_sharded) -> x_sharded`` applying ``t_total``
    steps in blocks of ``t_block`` with one deep-halo exchange per block.

    ``dim_to_axis`` maps tensor dims to mesh axis names, e.g. {0: 'data',
    1: 'model'} for a 2-D domain decomposition.
    """
    assert t_total % t_block == 0, "t_total must be a multiple of t_block"
    n_blocks = t_total // t_block
    h = spec.halo(t_block)
    pspec = P(*[dim_to_axis.get(d) for d in range(len(global_shape))])

    for d, ax in dim_to_axis.items():
        n_ax = _axis_size(mesh, ax)
        shard_len = global_shape[d] // n_ax
        assert global_shape[d] % n_ax == 0, (d, ax)
        assert h <= shard_len, (
            f"halo {h} exceeds shard extent {shard_len} on dim {d}; "
            f"reduce t_block or the mesh axis")

    def shard_fn(local: jnp.ndarray) -> jnp.ndarray:
        for _ in range(n_blocks):
            ext = local
            origins = {}
            for d, ax in dim_to_axis.items():
                ext = _exchange_one_axis(ext, d, h, ax, _axis_size(mesh, ax))
                origins[d] = (_axis_index(ax) * local.shape[d] - h)
            if inner == "stub":
                # kernel-adjusted accounting: on TPU the per-shard compute is
                # the VMEM-resident EBISU kernel (1 read + 1 write per cell
                # per block); the jnp inner materializes every tap shift.
                ext = ext * jnp.float32(0.999)
            else:
                ext = _blocked_steps(ext, spec, t_block, origins,
                                     global_shape)
            sl = [slice(None)] * ext.ndim
            for d in dim_to_axis:
                sl[d] = slice(h, ext.shape[d] - h)
            local = ext[tuple(sl)]
        return local

    # check_vma off: the halo-exchange body is per-shard-divergent by
    # design (edge shards differ)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(pspec,),
                       out_specs=pspec, check_vma=False)
    return jax.jit(fn), pspec
