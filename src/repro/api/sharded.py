"""Sharded deep-halo execution: ``StencilProgram.run_sharded`` over a mesh.

EBISU's thesis — low occupancy, large tiles, executed tile-by-tile — scales
out by treating **each device as one large tile**: the domain is
decomposed over a 1-D/2-D device mesh, and neighbor shards exchange ghost
zones **once per temporal block** of ``t`` fused steps, at halo depth
``t·radius``, instead of once per time step at depth ``radius`` (the
wavefront/ghost-layer temporal blocking of Wittmann, Hager & Wellein —
PAPERS.md).  Total halo *bytes* are unchanged (depth × 1/frequency), but
the number of collective rounds — the latency/synchronization term, the
distributed analogue of Eq 11's grid-sync count — drops by ``t``.

Execution of one temporal block of depth ``d`` (DESIGN.md §12):

  1. **deep-halo gather** — for every sharded tensor dim, exchange
     ``h = d·radius``-deep slabs with both mesh neighbors via
     ``lax.ppermute`` (one round per dim per block).  Axes are extended
     sequentially on the progressively extended array, so box-stencil
     corner values arrive via the standard two-hop trick — the mesh-level
     analogue of the up-to-27 rim sub-block views the 3-D kernel fetches
     per tile (``stencil3d.py`` §9.2).  Boundary handling at the domain
     edge: *periodic* closes the ppermute ring (torus seam), *dirichlet*
     leaves the open chain's zero fill (exact for the shifted field),
     *reflect* self-mirrors the edge shard's own rim.
  2. **per-shard trapezoid** — ``d`` valid-mode steps of the shared tap
     engine (``taps.chain_trapezoid``) narrow the haloed block by one
     radius per step along every extended dim: step ``s`` computes only
     cells that can still reach the block's output, and after ``d`` steps
     the extent is exactly the shard again — gather and crop are the same
     geometry, no separate crop pass.
  3. **carry** — the result is the next block's input; all blocks of a
     ``T``-step run live under ONE cached jit (donated on backends that
     support it), exactly like ``StencilProgram.run``.

The per-shard compute is the jnp tap-engine chain (the same numerical
core the Pallas kernels and the oracle share, DESIGN.md §8.3); driving
the Pallas kernels *inside* shard_map needs a per-shard scalar-prefetch
origin operand and stays a recorded stretch item (DESIGN.md §17).

Everything here is importable without initializing a JAX backend; device
questions are answered when ``compile_stencil(..., mesh=)`` resolves the
mesh.  See ``docs/sharding.md`` for the user-facing guide.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.distributed import _exchange_one_axis
from repro.core.stencil_spec import StencilSpec
from repro.kernels.taps import engine_for, tap_sum

__all__ = [
    "count_ppermutes",
    "mesh_key",
    "planned_exchange_rounds",
    "resolve_mesh",
    "shard_extents",
    "sharded_partition_spec",
    "validate_mesh_for",
]


# ============================================================ mesh plumbing ==
def resolve_mesh(mesh, ndim: int) -> Mesh | None:
    """Normalize the ``compile_stencil(..., mesh=)`` argument to a Mesh.

    Accepted forms (mesh axis ``k`` shards tensor dim ``k``):

      * ``None``            — single-device program (no sharding),
      * ``jax.sharding.Mesh`` — used as-is (at most ``ndim`` axes),
      * ``int n``           — 1-D mesh ``(n,)`` sharding dim 0,
      * ``tuple`` of ints   — e.g. ``(2, 4)`` shards dims 0 and 1.

        mesh = resolve_mesh((2, 4), ndim=3)    # Mesh('shard0': 2, 'shard1': 4)

    Int/tuple forms construct the mesh over ``jax.devices()`` (see
    ``repro.launch.mesh.make_stencil_mesh``) — this is the one place the
    sharded layer touches the backend.
    """
    if mesh is None:
        return None
    if isinstance(mesh, int):
        mesh = (mesh,)
    if isinstance(mesh, (tuple, list)):
        from repro.launch.mesh import make_stencil_mesh
        mesh = make_stencil_mesh(tuple(mesh))
    if not isinstance(mesh, Mesh):
        raise TypeError(
            f"mesh must be a jax.sharding.Mesh, an int, a tuple of ints, "
            f"or None; got {type(mesh).__name__}")
    if not (1 <= len(mesh.axis_names) <= ndim):
        raise ValueError(
            f"mesh has {len(mesh.axis_names)} axes but the stencil domain "
            f"is {ndim}-D; use a 1-D or up-to-{ndim}-D mesh (axis k shards "
            f"tensor dim k)")
    return mesh


def mesh_key(mesh: Mesh | None):
    """Hashable identity of a mesh for program/runner cache keys."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


def _mesh_dims(mesh: Mesh) -> tuple[int, ...]:
    """Shard count per mesh-covered tensor dim (dim k <- mesh axis k)."""
    return tuple(mesh.shape[ax] for ax in mesh.axis_names)


def shard_extents(shape: tuple[int, ...], mesh: Mesh) -> tuple[int, ...]:
    """Per-shard domain extents: ``shape[k] / mesh_axis_k`` on covered
    dims, the full extent on uncovered trailing dims.  Requires
    divisibility (checked by :func:`validate_mesh_for`)."""
    dims = _mesh_dims(mesh)
    return tuple(s // n for s, n in zip(shape, dims)) + shape[len(dims):]


def sharded_partition_spec(shape_len: int, mesh: Mesh) -> P:
    """The PartitionSpec ``run_sharded`` places its operand with: mesh
    axis ``k`` over tensor dim ``k``, trailing dims replicated."""
    axes = list(mesh.axis_names) + [None] * (shape_len - len(mesh.axis_names))
    return P(*axes)


def validate_mesh_for(spec: StencilSpec, shape: tuple[int, ...],
                      mesh: Mesh, t: int, boundary) -> None:
    """Refuse mesh/domain/depth combinations the one-hop deep-halo
    exchange cannot execute, with the fix spelled out:

      * every sharded dim must be divisible by its mesh axis (shards are
        uniform — XLA's sharded layout requires it);
      * the block halo ``t·radius`` must fit inside one neighbor shard
        (halo slabs travel exactly one ppermute hop per block);
      * reflect additionally mirrors ``t·radius`` interior cells about
        the edge *excluding* the edge cell, needing one extra row;
      * neumann is not wired into the shard-local edge fills yet —
        refused up front rather than KeyError-ing mid-compile.
    """
    if getattr(boundary, "kind", None) == "neumann":
        raise ValueError(
            f"{spec.name}: run_sharded does not support neumann boundaries "
            "yet (the shard-local edge ghost fill only implements "
            "dirichlet/periodic/reflect); use one of those, or run the "
            "program single-device where neumann is fully supported")
    dims = _mesh_dims(mesh)
    h = spec.halo(t)
    for d, n in enumerate(dims):
        if n == 1:
            continue
        if shape[d] % n:
            raise ValueError(
                f"{spec.name}: domain dim {d} ({shape[d]}) is not divisible "
                f"by mesh axis {mesh.axis_names[d]!r} ({n} shards); pad the "
                f"domain to a multiple of {n} or pick a mesh shape that "
                f"divides {shape[d]} (shards must be uniform)")
        shard = shape[d] // n
        need = h + 1 if getattr(boundary, "kind", None) == "reflect" else h
        if need > shard:
            raise ValueError(
                f"{spec.name}: block halo t*radius = {t}*{spec.radius} = {h} "
                f"{'(+1 for the reflect mirror) ' if need > h else ''}"
                f"exceeds the shard extent {shard} on dim {d} "
                f"({shape[d]} cells / {n} shards) — the deep-halo gather is "
                f"one neighbor hop per block.  Reduce t, use fewer shards "
                f"on mesh axis {mesh.axis_names[d]!r}, or grow the domain")


def planned_exchange_rounds(total_t: int, t: int) -> int:
    """Halo-exchange rounds a ``T``-step sharded run performs: one per
    temporal block (``ceil(T/t)`` via the remainder-sweep schedule) —
    versus ``T`` rounds for the classic exchange-every-step scheme.

        planned_exchange_rounds(64, 4)   # -> 16, an 4x round reduction
    """
    from repro.api.program import sweep_schedule
    return len(sweep_schedule(total_t, t))


# ====================================================== deep-halo execution ==
def _extend_local(x: jnp.ndarray, dim: int, h: int, boundary) -> jnp.ndarray:
    """Ghost-extend one *unsharded* dim by ``h`` with the boundary rule —
    the global edge lives entirely on this shard, so no exchange needed."""
    pad = [(0, 0)] * x.ndim
    pad[dim] = (h, h)
    mode = {"periodic": dict(mode="wrap"),
            "reflect": dict(mode="reflect")}[boundary.kind]
    return jnp.pad(x, pad, **mode)


def _mirror_rim(ext: jnp.ndarray, dim: int, h: int, lo: bool) -> jnp.ndarray:
    """The reflect ghost slab an edge shard fills from its own rim:
    ``ghost(-k) = u(k)`` about the edge cell (edge cell excluded)."""
    n = ext.shape[dim]
    idx = [slice(None)] * ext.ndim
    idx[dim] = slice(1, 1 + h) if lo else slice(n - 1 - h, n - 1)
    return jnp.flip(ext[tuple(idx)], axis=dim)


def _exchange_sharded_axis(ext: jnp.ndarray, dim: int, h: int, axis_name: str,
                           n: int, boundary) -> jnp.ndarray:
    """One deep-halo exchange round on a sharded dim (both directions).

    periodic: closed ppermute ring — the torus seam is just another
    neighbor hop.  dirichlet: open chain; edge shards keep ppermute's
    zero fill, which is exactly the ghost value of the *shifted* field.
    reflect: open chain, then edge shards overwrite their sourceless
    halo with the mirror of their own rim (a local flip, no traffic).
    """
    periodic = boundary.kind == "periodic"
    out = _exchange_one_axis(ext, dim, h, axis_name, n, periodic=periodic)
    if boundary.kind != "reflect" or n == 1:
        return out
    idx = jax.lax.axis_index(axis_name)
    lo_idx = [slice(None)] * out.ndim
    lo_idx[dim] = slice(0, h)
    hi_idx = [slice(None)] * out.ndim
    hi_idx[dim] = slice(out.shape[dim] - h, out.shape[dim])
    lo = jnp.where(idx == 0, _mirror_rim(ext, dim, h, lo=True),
                   out[tuple(lo_idx)])
    hi = jnp.where(idx == n - 1, _mirror_rim(ext, dim, h, lo=False),
                   out[tuple(hi_idx)])
    mid = [slice(None)] * out.ndim
    mid[dim] = slice(h, out.shape[dim] - h)
    return jnp.concatenate([lo, out[tuple(mid)], hi], axis=dim)


def _dirichlet_post(sharded_dims, axis_names, ns, shard_shape, rad, h):
    """The trapezoid ``post`` hook re-pinning the *global* Dirichlet
    boundary: after step ``s``, the surviving ghost band (``h − s·rad``
    deep, only on shards at the true domain edge) is re-zeroed so the
    next step reads boundary-true zeros, not evolved ghost garbage.
    Interior seams need nothing — their halo is true neighbor data
    evolving exactly."""

    def post(v: jnp.ndarray, s: int) -> jnp.ndarray:
        cur = h - s * rad
        if cur <= 0:
            return v
        mask = None
        for dim in sharded_dims:
            idx = jax.lax.axis_index(axis_names[dim])
            ids = jnp.arange(v.shape[dim])
            ok = (((ids >= cur) | (idx > 0))
                  & ((ids < shard_shape[dim] + cur) | (idx < ns[dim] - 1)))
            bshape = [1] * v.ndim
            bshape[dim] = v.shape[dim]
            ok = ok.reshape(bshape)
            mask = ok if mask is None else mask & ok
        return jnp.where(mask, v, jnp.zeros((), v.dtype))

    return post


def build_sharded_runner(prog, total_t: int):
    """The un-jitted global ``f(x) -> y`` for ``prog.run_sharded(x, T)``.

    One shard_map over the program's mesh; inside it, the full multi-
    block schedule (``sweep_schedule`` — full-depth blocks plus one
    shallower remainder block) with one deep-halo gather per block and
    the per-shard trapezoid chain per block.  Compute happens at the
    program's ``compute_dtype``; only the final result is cast back to
    storage.  Dirichlet(v≠0) runs through the same affine closure as the
    single-device chain (DESIGN.md §11.3): the carry is shifted by ``v``
    into zero-Dirichlet space around every block and re-shifted by
    ``v·s^d`` after it — exact when ``s = 1`` (any depth) or ``d = 1``
    (validated at compile).
    """
    from repro.api.program import _grouped, sweep_schedule

    spec, mesh, boundary = prog.spec, prog.mesh, prog.boundary
    depth = max(1, min(prog.t, total_t))
    groups = _grouped(sweep_schedule(total_t, depth))
    rad = spec.radius
    ndim = spec.ndim
    axis_names = list(mesh.axis_names) + [None] * (ndim - len(mesh.axis_names))
    ns = list(_mesh_dims(mesh)) + [1] * (ndim - len(mesh.axis_names))
    sharded_dims = [d for d in range(ndim) if ns[d] > 1]
    shard_shape = shard_extents(prog.shape, mesh)
    cdtype = prog.compute_dtype
    s = tap_sum(spec.taps)
    engine = engine_for(spec.taps, ndim)
    pspec = sharded_partition_spec(ndim, mesh)
    dirichlet = boundary.kind == "dirichlet"
    shift = boundary.value if dirichlet else 0.0

    def block(v: jnp.ndarray, d: int) -> jnp.ndarray:
        """One temporal block: gather a d*rad halo once, run d narrowed
        steps; output extent == shard extent again."""
        h = rad * d
        if dirichlet and shift != 0.0:
            v = v - jnp.asarray(shift, cdtype)
        ext = v
        for dim in sharded_dims:
            ext = _exchange_sharded_axis(ext, dim, h, axis_names[dim],
                                        ns[dim], boundary)
        if dirichlet:
            # unsharded dims stay unextended: the tap engine's zero-fill
            # IS the (shifted) Dirichlet condition at the true array edge
            out = engine.chain_trapezoid(
                ext, d, axes=sharded_dims,
                post=_dirichlet_post(sharded_dims, axis_names, ns,
                                     shard_shape, rad, h))
        else:
            for dim in range(ndim):
                if dim not in sharded_dims:
                    ext = _extend_local(ext, dim, h, boundary)
            out = engine.chain_trapezoid(ext, d, axes=tuple(range(ndim)))
        if dirichlet and shift != 0.0:
            out = out + jnp.asarray(shift * s ** d, cdtype)
        return out

    def shard_fn(local: jnp.ndarray) -> jnp.ndarray:
        v = local
        for d, count in groups:
            for _ in range(count):
                v = block(v, d)
        return v

    # check_vma off: edge shards run a different exchange than interior ones
    mapped = jax.shard_map(shard_fn, mesh=mesh, in_specs=(pspec,),
                           out_specs=pspec, check_vma=False)

    def run(x: jnp.ndarray) -> jnp.ndarray:
        return mapped(x.astype(cdtype)).astype(prog.dtype)

    return run


def operand_sharding(prog) -> NamedSharding:
    """The NamedSharding ``run_sharded`` places its operand with."""
    return NamedSharding(prog.mesh,
                         sharded_partition_spec(prog.spec.ndim, prog.mesh))


# ========================================================== introspection ==
def _walk_jaxprs(obj):
    """Yield every (Closed)Jaxpr reachable from an eqn param value.

    Duck-typed (``eqns`` / ``.jaxpr.eqns``) so it survives the move of
    Jaxpr/ClosedJaxpr between ``jax.core`` homes across versions.
    """
    if hasattr(obj, "eqns"):                        # a Jaxpr
        yield obj
    elif hasattr(obj, "jaxpr") and hasattr(getattr(obj, "jaxpr"), "eqns"):
        yield obj.jaxpr                             # a ClosedJaxpr
    elif isinstance(obj, (tuple, list)):
        for o in obj:
            yield from _walk_jaxprs(o)


def _count_primitive(jaxpr, name: str) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for v in eqn.params.values():
            for sub in _walk_jaxprs(v):
                n += _count_primitive(sub, name)
    return n


def count_ppermutes(fn, *args) -> int:
    """Number of ``ppermute`` collectives in the trace of ``fn(*args)`` —
    what the exchange-count tests assert against
    ``planned_exchange_rounds(T, t) × 2 × (#sharded axes)``.

        fn = build_sharded_runner(prog, total_t=16)
        count_ppermutes(fn, x)    # e.g. 4 blocks × 2 dirs × 1 axis = 8
    """
    closed = jax.make_jaxpr(fn)(*args)
    return _count_primitive(closed.jaxpr, "ppermute")
