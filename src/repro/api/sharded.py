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

Each shard runs one of two executors (DESIGN.md §12):

**Sweep kernel** (3-D, zero Dirichlet: :func:`uses_kernel`).  Each shard
keeps a *window* of the global field in ``ebisu3d_padded``'s padded
layout for the whole call.  On every sharded dim the window has the
uniform extent ``S + 2h`` (``h = t·radius``) and is aligned to the global
edge: the first shard's window is ``[0, S+2h)``, the last one's
``[G−S−2h, G)``, an interior one's ``[o−h, o+S+h)``.  A global edge thus
always lies on the array edge, where the kernel's own zero fill is the
exact Dirichlet condition; the error the zero fill makes at a window's
inner edge travels ``h`` cells in a block and never reaches the shard's
own cells.  The first exchange round builds the window (``2h``-deep
slabs); every later round refreshes only the ``h`` cells next to each
inner edge — the cells the last block left wrong — by slicing them from
the neighbor's window, ``ppermute``-ing them and writing them in place
(``lax.dynamic_update_slice``, offsets from ``lax.axis_index``), so no
block copies the shard.  One layout per call, one crop at the end; a
shard must hold ``2h`` cells on each sharded dim (:func:`min_shard`).

**Trapezoid** (every other boundary, and 2-D).  Per block of depth ``d``:

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
  3. **carry** — the result is the next block's input.

Both run every block of a ``T``-step call under ONE cached jit (the
operand donated on backends that support it), exactly like
``StencilProgram.run``, and both exchange dims in sequence with two
directional ``ppermute``s per sharded dim per block.  Each round runs
under the device scope ``stencil.exchange``.

Everything here is importable without initializing a JAX backend; device
questions are answered when ``compile_stencil(..., mesh=)`` resolves the
mesh.  See ``docs/sharding.md`` for the user-facing guide.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.core.distributed import _exchange_one_axis
from repro.core.stencil_spec import StencilSpec
from repro.kernels.taps import engine_for, is_zero_dirichlet, tap_sum

__all__ = [
    "count_ppermutes",
    "halo_bytes",
    "max_depth",
    "mesh_key",
    "min_shard",
    "planned_exchange_rounds",
    "resolve_mesh",
    "shard_extents",
    "sharded_partition_spec",
    "uses_kernel",
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


def uses_kernel(spec: StencilSpec, boundary) -> bool:
    """Whether ``run_sharded`` runs the 3-D sweep kernel on each shard
    (3-D, zero Dirichlet) rather than the jnp trapezoid."""
    return spec.ndim == 3 and is_zero_dirichlet(boundary)


def min_shard(spec: StencilSpec, t: int, boundary) -> int:
    """The smallest shard extent on a sharded dim at depth ``t``: the
    kernel path's edge window takes ``2h`` cells from its neighbor
    (``h = t·radius``); the trapezoid takes ``h``, and reflect mirrors
    one more."""
    h = spec.halo(t)
    if uses_kernel(spec, boundary):
        return 2 * h
    return h + 1 if getattr(boundary, "kind", None) == "reflect" else h


def _depth_cap(spec: StencilSpec, shard: int, boundary) -> int:
    """The deepest ``t`` whose :func:`min_shard` fits ``shard`` cells."""
    r = spec.radius
    if uses_kernel(spec, boundary):
        return shard // (2 * r)
    if getattr(boundary, "kind", None) == "reflect":
        return (shard - 1) // r
    return shard // r


def max_depth(spec: StencilSpec, shape: tuple[int, ...],
              counts: tuple[int, ...], boundary) -> int | None:
    """The deepest ``t`` every sharded dim holds, with ``counts[k]``
    shards on dim ``k`` (at least 1: :func:`validate_mesh_for` refuses
    what even 1 exceeds); ``None`` where no dim is sharded."""
    caps = [_depth_cap(spec, s // n, boundary)
            for s, n in zip(shape, counts) if n > 1]
    return max(1, min(caps)) if caps else None


def validate_mesh_for(spec: StencilSpec, shape: tuple[int, ...],
                      mesh: Mesh, t: int, boundary) -> None:
    """Refuse mesh/domain/depth combinations the one-hop deep-halo
    exchange cannot execute, with the fix spelled out:

      * every sharded dim must be divisible by its mesh axis (shards are
        uniform — XLA's sharded layout requires it);
      * the block halo ``t·radius`` must fit inside one neighbor shard
        (halo slabs travel exactly one ppermute hop per block);
      * the sweep-kernel path (3-D zero Dirichlet) needs ``2·t·radius``:
        an edge shard's window reaches that deep into its neighbor;
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
    need = min_shard(spec, t, boundary)
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
        if need > shard:
            why = ("x 2 (the sweep kernel's edge window) "
                   if uses_kernel(spec, boundary) else
                   "(+1 for the reflect mirror) " if need > h else "")
            t_max = max(1, _depth_cap(spec, shard, boundary))
            raise ValueError(
                f"{spec.name}: block halo t*radius = {t}*{spec.radius} = {h} "
                f"{why}exceeds the shard extent {shard} on dim {d} "
                f"({shape[d]} cells / {n} shards) — the deep-halo gather is "
                f"one neighbor hop per block.  Reduce t to at most "
                f"{t_max}, use fewer shards on mesh axis "
                f"{mesh.axis_names[d]!r}, or grow the domain to "
                f"{n * need} cells on dim {d}")


def planned_exchange_rounds(total_t: int, t: int) -> int:
    """Halo-exchange rounds a ``T``-step sharded run performs: one per
    temporal block (``ceil(T/t)`` via the remainder-sweep schedule) —
    versus ``T`` rounds for the classic exchange-every-step scheme.

        planned_exchange_rounds(64, 4)   # -> 16, an 4x round reduction
    """
    from repro.api.program import sweep_schedule
    return len(sweep_schedule(total_t, t))


def halo_bytes(prog, total_t: int) -> int:
    """Bytes the exchange rounds of one ``run_sharded(x, total_t)`` call
    send between devices, all shards together: per round and sharded dim,
    one slab each way over every neighbor pair (the first round of the
    kernel path sends ``2h``-deep slabs, its later ones ``h``)."""
    from repro.api.program import sweep_schedule

    _, ns, dims = _mesh_layout(prog)
    shard = shard_extents(prog.shape, prog.mesh)
    schedule = sweep_schedule(total_t, max(1, min(prog.t, total_t)))
    kernel = uses_kernel(prog.spec, prog.boundary)
    ring = getattr(prog.boundary, "kind", None) == "periodic"
    h = prog.spec.halo(schedule[0]) if schedule else 0
    window = [n + 2 * h if j in dims else n for j, n in enumerate(shard)]
    total = 0
    for k, d in enumerate(schedule):
        # the extents the round's slabs are cut from, dim after dim
        ext = window if kernel and k > 0 else list(shard)
        for dim in dims:
            deep = (2 * h if k == 0 else h) if kernel else prog.spec.halo(d)
            cells = deep * math.prod(n for j, n in enumerate(ext) if j != dim)
            pairs = (ns[dim] if ring else ns[dim] - 1) * \
                (prog.mesh.size // ns[dim])
            total += 2 * pairs * cells
            if k == 0 or not kernel:
                ext[dim] = window[dim] if kernel else shard[dim] + 2 * deep
    return total * prog.compute_dtype.itemsize


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


def _mesh_layout(prog):
    """(mesh axis name, shard count) per tensor dim, and the sharded dims."""
    ndim, mesh = prog.spec.ndim, prog.mesh
    pad = [None] * (ndim - len(mesh.axis_names))
    names = list(mesh.axis_names) + pad
    ns = list(_mesh_dims(mesh)) + [1] * len(pad)
    return names, ns, [d for d in range(ndim) if ns[d] > 1]


def _trapezoid_shards(prog, total_t: int):
    """The per-shard ``f(local) -> local`` of the jnp trapezoid: one
    deep-halo gather and ``d`` narrowed tap-engine steps per block.
    Dirichlet(v≠0) runs through the same affine closure as the
    single-device chain (DESIGN.md §11.3): the carry is shifted by ``v``
    into zero-Dirichlet space around every block and re-shifted by
    ``v·s^d`` after it — exact when ``s = 1`` (any depth) or ``d = 1``
    (validated at compile)."""
    from repro.api.program import _grouped, sweep_schedule

    spec, boundary = prog.spec, prog.boundary
    depth = max(1, min(prog.t, total_t))
    groups = _grouped(sweep_schedule(total_t, depth))
    rad = spec.radius
    ndim = spec.ndim
    axis_names, ns, sharded_dims = _mesh_layout(prog)
    shard_shape = shard_extents(prog.shape, prog.mesh)
    cdtype = prog.compute_dtype
    s = tap_sum(spec.taps)
    engine = engine_for(spec.taps, ndim)
    dirichlet = boundary.kind == "dirichlet"
    shift = boundary.value if dirichlet else 0.0

    def block(v: jnp.ndarray, d: int) -> jnp.ndarray:
        """One temporal block: gather a d*rad halo once, run d narrowed
        steps; output extent == shard extent again."""
        h = rad * d
        if dirichlet and shift != 0.0:
            v = v - jnp.asarray(shift, cdtype)
        ext = v
        with telemetry.scope("exchange"):
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

    return shard_fn


def _own_offset(i, n: int, h: int):
    """Where shard ``i``'s own cells start in its edge-aligned window:
    0 for the first shard, ``2h`` for the last, ``h`` between."""
    return jnp.where(i == 0, 0, jnp.where(i == n - 1, 2 * h, h))


def _kernel_shards(prog, total_t: int, aligned: bool):
    """The per-shard ``f(local) -> local`` of the sweep-kernel path: the
    edge-aligned window in the padded layout, ``ebisu3d_padded`` per
    block, the ``h`` cells at each inner edge refreshed in place between
    blocks (module docstring)."""
    from repro.api.program import (_grouped, _sweep_tile_3d, plan_bucketed,
                                   sweep_schedule)
    from repro.kernels.stencil3d import ebisu3d_padded, padded_shape_3d

    spec, interpret = prog.spec, prog.interpret
    depth = max(1, min(prog.t, total_t))
    groups = _grouped(sweep_schedule(total_t, depth))
    h = spec.halo(depth)
    names, ns, sharded_dims = _mesh_layout(prog)
    shard = shard_extents(prog.shape, prog.mesh)
    win = tuple(n + 2 * h if d in sharded_dims else n
                for d, n in enumerate(shard))
    plan = prog.plan or plan_bucketed(spec, shard, prog.hw)
    cfg = {d: _sweep_tile_3d(spec, d, win, prog.hw, plan, interpret,
                             aligned)
           for d, _ in groups}

    def own(dim: int):
        return _own_offset(jax.lax.axis_index(names[dim]), ns[dim], h)

    def layout(local: jnp.ndarray) -> jnp.ndarray:
        """The first round: each sharded dim extended by ``2h``-deep
        neighbor slabs, then cut to the shard's window."""
        v = local
        for dim in sharded_dims:
            ext = _exchange_sharded_axis(v, dim, 2 * h, names[dim], ns[dim],
                                        prog.boundary)
            v = jax.lax.dynamic_slice_in_dim(ext, 2 * h - own(dim),
                                             win[dim], axis=dim)
        return v

    def slab(xp: jnp.ndarray, dim: int, start) -> jnp.ndarray:
        """The ``h`` window cells from ``start`` on ``dim``."""
        starts = [0] * 3
        starts[dim] = start
        sizes = list(win)
        sizes[dim] = h
        return jax.lax.dynamic_slice(xp, starts, sizes)

    def put(xp: jnp.ndarray, v: jnp.ndarray, dim: int, start: int):
        starts = [0] * 3
        starts[dim] = start
        return jax.lax.dynamic_update_slice(xp, v, starts)

    def refresh(xp: jnp.ndarray) -> jnp.ndarray:
        """A later round: the ``h`` cells at each inner window edge, which
        the last block left wrong, from the neighbor that owns them."""
        for dim in sharded_dims:
            n, name, w = ns[dim], names[dim], win[dim]
            i = jax.lax.axis_index(name)
            mine = _own_offset(i, n, h)
            # neighbor i+1's window starts shard - own(i+1) + own(i) cells
            # into this one; neighbor i-1's ends h + own(i) - own(i-1) in
            up = slab(xp, dim, shard[dim] + mine - _own_offset(i + 1, n, h))
            down = slab(xp, dim, h + mine - _own_offset(i - 1, n, h))
            from_prev = jax.lax.ppermute(up, name,
                                         [(j, j + 1) for j in range(n - 1)])
            from_next = jax.lax.ppermute(down, name,
                                         [(j + 1, j) for j in range(n - 1)])
            # a global edge has no neighbor: its cells are the shard's own
            xp = put(xp, jnp.where(i > 0, from_prev, slab(xp, dim, 0)),
                     dim, 0)
            xp = put(xp, jnp.where(i < n - 1, from_next,
                                   slab(xp, dim, w - h)), dim, w - h)
        return xp

    def shard_fn(local: jnp.ndarray) -> jnp.ndarray:
        with telemetry.scope("exchange"):
            v = layout(local)
        xp, first = None, True
        for d, count in groups:
            zc, ty, tx, batch = cfg[d]
            shape = padded_shape_3d(spec, d, win, zc=zc, ty=ty, tx=tx,
                                    aligned=aligned)
            if xp is None or xp.shape != shape:
                with telemetry.scope("pad"):
                    if xp is not None:
                        v = xp[:win[0], :win[1], :win[2]]
                    xp = jnp.pad(v, [(0, p - n) for p, n in zip(shape, win)])
            for _ in range(count):
                if not first:
                    with telemetry.scope("exchange"):
                        xp = refresh(xp)
                first = False
                with telemetry.scope("sweep"):
                    xp = ebisu3d_padded(
                        xp, spec, d, zdim=win[0], ydim=win[1], xdim=win[2],
                        zc=zc, ty=ty, tx=tx, lazy_batch=batch,
                        interpret=interpret, aligned=aligned)
        with telemetry.scope("crop"):
            starts = [own(dim) if dim in sharded_dims else 0
                      for dim in range(3)]
            return jax.lax.dynamic_slice(xp, starts, shard)

    return shard_fn


def shard_mapped(prog, shard_fn):
    """``shard_fn`` over the program's mesh, one shard a device."""
    pspec = sharded_partition_spec(prog.spec.ndim, prog.mesh)
    # check_vma off: edge shards run a different exchange than interior ones
    return jax.shard_map(shard_fn, mesh=prog.mesh, in_specs=(pspec,),
                         out_specs=pspec, check_vma=False)


def build_sharded_runner(prog, total_t: int):
    """The un-jitted global ``f(x) -> y`` for ``prog.run_sharded(x, T)``.

    One shard_map over the program's mesh; inside it, the full multi-
    block schedule (``sweep_schedule`` — full-depth blocks plus one
    shallower remainder block), one exchange round per block, and per
    shard the sweep kernel (:func:`uses_kernel`; its Mosaic layout and
    body wherever Mosaic lowers it) or the trapezoid chain.  Compute
    happens at the program's ``compute_dtype``; only the final result is
    cast back to storage.
    """
    if uses_kernel(prog.spec, prog.boundary):
        shard_fn = _kernel_shards(prog, total_t, not prog.interpret)
    else:
        shard_fn = _trapezoid_shards(prog, total_t)
    mapped = shard_mapped(prog, shard_fn)

    def run(x: jnp.ndarray) -> jnp.ndarray:
        with telemetry.scope("cast"):
            w = x.astype(prog.compute_dtype)
        y = mapped(w)
        with telemetry.scope("cast"):
            return y.astype(prog.dtype)

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
