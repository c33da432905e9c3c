"""EBISU-3D Pallas kernel: lazy-batched z-streaming through VMEM queues.

This is the paper's Fig. 5/6 scheme on the TPU memory hierarchy, with the
§6 planner's decisions wired all the way in:

  * Each Pallas grid step is a *device tile*: a chunk of ``zc`` output
    planes × a ``(ty, tx)`` in-plane tile (``plan.block``).  The grid is
    ``(gz, gy, gx)`` — the planner's §6.4 deeper-or-wider choice is
    executed, not decorative: large domains run at planner-chosen XY
    tiles instead of whatever pads into VMEM.
  * **Halo-exact fetching on every blocked axis**: the tile's context
    comes from one ``halo``-deep sub-block per side, selected by
    halo-granular BlockSpecs (``HALO = t·rad``) — input traffic per grid
    step is ``(zc + 2·halo) × (ty + 2·halo) × (tx + 2·halo)`` cells, not
    whole neighbor blocks.  Each tiled axis is rounded up to a multiple
    of ``halo`` so its rim sub-blocks are block-aligned (DESIGN.md §8.4,
    §9.2).  An axis whose tile covers the whole domain stays *untiled*:
    no rim views, and the zero-fill slicing edge is its Dirichlet
    boundary for free (DESIGN.md §8.2).
  * Inside the kernel, planes stream through a **multi-queue**: one
    sliding window of ``W = B + 2·rad`` planes per temporal step, held in
    VMEM scratch (padded to (8, 128) lane alignment).  This is the
    paper's *shifting* addressing mode (§4.2.2) batched by
    ``B = lazy_batch`` planes: per pipeline stage the window shifts by
    ``B`` and one *batched* vectorized tap application
    (``taps.TapEngine.window_step``) advances ``B`` planes of a temporal
    step at once — lazy streaming with honest batch granularity instead
    of a plane-at-a-time ``fori_loop``.
  * On tiled in-plane axes the cascade is **trapezoid-narrowed**
    (DESIGN.md §9.1): the time-``s`` planes carry only the
    ``tile + 2·(t−s)·rad`` live extent, computed in valid mode from the
    fetched halo — per-step in-plane FLOPs shrink with depth instead of
    recomputing the full haloed tile every step.
  * When input planes ``[z, z+B)`` (time 0) are enqueued, planes
    ``[z - s·rad, z+B - s·rad)`` of time ``s`` become computable —
    dequeue of step ``s`` overlaps enqueue of step ``s+1`` ("seamless
    time-step transitions").  The whole schedule is statically unrolled
    (``(zc + 2·halo)/B`` stages), so every queue access is a static
    slice — no dynamic ring arithmetic on the hot path.
  * The final time step is written straight to the output block — lazy
    streaming's "one sync per tile": a grid step has a single pipeline
    boundary regardless of depth ``t``.

**Aligned launches** (``aligned=True``, always when Mosaic lowers the
kernel): in-plane blocks obey the TPU's (8, 128) tiling — a tiled y
axis fetches rims of ``round8(halo)`` rows, a tiled x axis rims of
``round128(halo)`` lanes (z is a leading block axis and keeps exact
``halo`` rims).  The streamer advances one plane per ``lax.fori_loop``
iteration through power-of-two rings (the paper's *computing*
addressing, ``slot = z & (ring − 1)``), with an inner loop over the
``t`` queues, so the generated code is one plane step whatever ``t`` and
the chunk depth — the compile takes seconds.  Planes carry one zero
sublane tile of row margin per side and shift by ``pltpu.roll``
(``taps.apply_taps_rows``); the in-plane extent is not narrowed per step
(the overlapped rims absorb the edge error instead).

Boundary semantics: zero outside the domain at every step.  The domain
sits at ``[0, zdim) × [0, ydim) × [0, xdim)`` of the padded array; the
per-batch {0,1} validity factors (global-z × global-y × global-x, the
latter two only on tiled axes) are applied as broadcast multiplies per
batched tap application (DESIGN.md §8.1-2, §9.2).  Queue windows are
zero-initialized so strip planes below the chunk read as the tap
engine's zero-fill — garbage in the out-of-strip "error zone" decays
before it can reach an output plane (DESIGN.md §8.3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.multiqueue import stream_schedule
from repro.core.planner import next_pow2
from repro.core.stencil_spec import StencilSpec
from repro.kernels.stencil2d import vmem_limit
from repro.kernels.taps import (MARGIN, apply_taps_rows, check_boundary,
                                engine_for, group_by_leading,
                                is_zero_dirichlet, with_boundary)


def _pad_to(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def chunk_geometry(spec: StencilSpec, t: int, zc: int) -> tuple[int, int]:
    """Resolve the (zc, halo) a 3-D launch will actually use.

    ``zc`` is raised to at least one halo and rounded up to a multiple of
    ``halo`` so the rim sub-blocks of the halo-exact fetch are aligned.
    """
    halo = spec.halo(t)
    zc = max(zc, halo)
    return _pad_to(zc, halo), halo


def xy_tile(spec: StencilSpec, t: int, dim: int, tile: int | None,
            align: int = 1) -> tuple[int, bool]:
    """Resolve a requested in-plane tile: (extent, tiled?).

    The axis' rim is the halo rounded up to ``align`` (8 for y and 128
    for x on an aligned launch), and the tile is a multiple of the rim.
    ``None`` (or a tile that covers the domain once rounded) means the
    axis is untiled — full extent, no rim views.
    """
    if tile is None:
        return dim, False
    rim = _pad_to(spec.halo(t), align)
    tile = _pad_to(max(tile, rim), rim)
    if tile >= dim:
        return dim, False
    return tile, True


def _aligns(aligned: bool) -> tuple[int, int]:
    """(y, x) rim granularity: the f32 (8, 128) tile on aligned launches."""
    return (8, 128) if aligned else (1, 1)


def input_planes_per_chunk(spec: StencilSpec, t: int, zc: int) -> tuple[int, int]:
    """Modeled input traffic: (planes fetched per chunk, chunk body planes)."""
    zc, halo = chunk_geometry(spec, t, zc)
    return zc + 2 * halo, zc


def launch_geometry_3d(spec: StencilSpec, t: int, shape: tuple[int, int, int],
                       *, zc: int = 16, ty: int | None = None,
                       tx: int | None = None, aligned: bool = False) -> dict:
    """The geometry a 3-D launch will actually execute (no tracing).

    Returns grid, per-grid-step block, halo, per-axis tiled flags, the
    padded array shape, and the fetched/body cell counts per grid step
    (rims rounded to the (8, 128) tile on an ``aligned`` launch, which
    also fetches whole padded planes on untiled axes) — the quantities
    the bench's traffic model and the planner-honoring tests consume.
    """
    zdim, ydim, xdim = shape
    zc, halo = chunk_geometry(spec, t, zc)
    ay, ax = _aligns(aligned)
    ty_r, tiled_y = xy_tile(spec, t, ydim, ty, ay)
    tx_r, tiled_x = xy_tile(spec, t, xdim, tx, ax)
    zp = _pad_to(zdim, zc)
    yp = _pad_to(ydim, ty_r) if tiled_y else _pad_to(ydim, 8)
    xp = _pad_to(xdim, tx_r) if tiled_x else _pad_to(xdim, 128)
    grid = (zp // zc,
            yp // ty_r if tiled_y else 1,
            xp // tx_r if tiled_x else 1)
    ry, rx = _pad_to(halo, ay), _pad_to(halo, ax)
    sy = ty_r + 2 * ry if tiled_y else (yp if aligned else ydim)
    sx = tx_r + 2 * rx if tiled_x else (xp if aligned else xdim)
    fetched = (zc + 2 * halo) * sy * sx
    body = zc * ty_r * tx_r
    return dict(grid=grid, block=(zc, ty_r, tx_r), halo=halo,
                tiled=(True, tiled_y, tiled_x), padded=(zp, yp, xp),
                fetched_cells=fetched, body_cells=body)


def _stream_kernel(*args, taps, t: int, rad: int, zc: int, halo: int,
                   batch: int, zdim: int, ydim: int, xdim: int,
                   ty: int, tx: int, nyk: int, nxk: int):
    refs, out_ref, buf = args[:-2], args[-2], args[-1]
    iz, iy, ix = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    engine = engine_for(taps, 3)
    # compute dtype policy: the kernel computes in the dtype of the padded
    # buffer it was handed (the scratch windows are allocated to match)
    cdtype = buf.dtype
    tiled_y, tiled_x = nyk == 3, nxk == 3
    kz = zc // halo
    sz = zc + 2 * halo
    sy = ty + 2 * halo if tiled_y else ydim
    sx = tx + 2 * halo if tiled_x else xdim
    cy = rad if tiled_y else 0          # per-step in-plane narrowing
    cx = rad if tiled_x else 0
    w = batch + 2 * rad
    z_base = iz * zc - halo             # global z of strip plane 0
    y_base = iy * ty - halo if tiled_y else 0
    x_base = ix * tx - halo if tiled_x else 0
    by, bx = out_ref.shape[1], out_ref.shape[2]

    def view(zi: int, yi: int, xi: int):
        return refs[(zi * nyk + yi) * nxk + xi]

    def ey(s: int) -> int:              # live y extent of time-s planes
        return sy - 2 * s * cy

    def ex(s: int) -> int:
        return sx - 2 * s * cx

    def apply_masks(planes: jnp.ndarray, p0: int, s: int) -> jnp.ndarray:
        """Dirichlet validity of time-s strip planes [p0, p0+n): global-z
        always (the z boundary moves with the grid step), global-y/x only
        on tiled axes (untiled axes are domain-cropped — their zero-fill
        edge is the boundary)."""
        n = planes.shape[0]
        zg = z_base + p0 + jax.lax.broadcasted_iota(jnp.int32, (n, 1, 1), 0)
        planes = planes * ((zg >= 0) & (zg < zdim)).astype(cdtype)
        if tiled_y:
            yg = (y_base + s * rad
                  + jax.lax.broadcasted_iota(jnp.int32, (1, ey(s), 1), 1))
            planes = planes * ((yg >= 0) & (yg < ydim)).astype(cdtype)
        if tiled_x:
            xg = (x_base + s * rad
                  + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ex(s)), 2))
            planes = planes * ((xg >= 0) & (xg < xdim)).astype(cdtype)
        return planes

    def slab(j_sub: int) -> jnp.ndarray:
        """Halo sub-block ``j_sub`` of the haloed z extent, assembled
        in-plane from the per-axis rim/body views and cropped to the
        tile's working extent."""
        if j_sub == 0:
            zi, zsl = 0, slice(None)
        elif j_sub <= kz:
            zi, zsl = 1, slice((j_sub - 1) * halo, j_sub * halo)
        else:
            zi, zsl = 2, slice(None)
        rows = []
        for yi in range(nyk):
            cells = [view(zi, yi, xi)[zsl] for xi in range(nxk)]
            rows.append(cells[0] if nxk == 1
                        else jnp.concatenate(cells, axis=2))
        plane = rows[0] if nyk == 1 else jnp.concatenate(rows, axis=1)
        return plane[:, :sy, :sx]

    # Queue windows are per-grid-step state.  Only the tail-source slice
    # [batch, w) must be zeroed: the first shift of each queue copies it to
    # the window head, where it stands in for the planes below the strip —
    # the zero-fill edge (DESIGN.md §8.3); the rest is overwritten before
    # it is ever read.
    buf[:, batch:w] = jnp.zeros((t, w - batch) + buf.shape[2:], cdtype)

    def advance(queue: int, planes: jnp.ndarray) -> None:
        """Shift queue's window by one batch (paper's 'shifting' mode).
        Queue ``q`` holds time-``q`` planes at their narrowed extent, in
        the scratch buffer's aligned corner."""
        ny, nx = ey(queue), ex(queue)
        tail = buf[queue, batch:w, :ny, :nx]
        buf[queue, 0:2 * rad, :ny, :nx] = tail
        buf[queue, 2 * rad:w, :ny, :nx] = planes

    for n in range(sz // batch):
        z0 = n * batch
        # ---- batched enqueue of input planes [z0, z0+batch) into queue 0.
        # A batch is whole halo-sub-blocks, each living in exactly one
        # z-view; in-plane each sub-block is one rim/body/rim concat.
        chunks = [slab(j) for j in range(z0 // halo, (z0 + batch) // halo)]
        newp = (chunks[0] if len(chunks) == 1
                else jnp.concatenate(chunks, axis=0)).astype(cdtype)
        advance(0, apply_masks(newp, z0, 0))

        # ---- cascade: one batched tap application per temporal step -----
        for s in range(1, t + 1):
            p0 = z0 - s * rad            # first plane this step produces
            window = buf[s - 1, :, :ey(s - 1), :ex(s - 1)]
            planes = engine.window_step(window, batch,
                                        inplane_crops=(cy, cx))
            planes = apply_masks(planes, p0, s)
            if s < t:
                advance(s, planes)
            else:
                lo, hi = max(p0, halo), min(p0 + batch, halo + zc)
                if lo < hi:
                    body = planes[lo - p0:hi - p0]
                    body = jnp.pad(body, ((0, 0), (0, by - ey(t)),
                                          (0, bx - ex(t))))
                    out_ref[lo - halo:hi - halo] = body.astype(out_ref.dtype)


def _stream_kernel_aligned(*args, taps, t: int, rad: int, zc: int,
                           halo: int, zdim: int, ydim: int, xdim: int,
                           ty: int, tx: int, ry: int, rx: int,
                           nyk: int, nxk: int):
    """The Mosaic body.  The scratch ``q`` is ``(t, ring, MARGIN + SY +
    MARGIN, SX)``: queue ``s`` holds time-``s`` planes of the haloed
    ``SY × SX`` working extent, slot ``plane & (ring − 1)``."""
    refs, out_ref, q = args[:-2], args[-2], args[-1]
    iz, iy, ix = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tiled_y, tiled_x = nyk == 3, nxk == 3
    m = MARGIN
    ring = q.shape[1]
    sy, sx = q.shape[2] - 2 * m, q.shape[3]
    by, bx = out_ref.shape[1], out_ref.shape[2]
    oy, ox = (ry if tiled_y else 0), (rx if tiled_x else 0)
    z_base = iz * zc - halo             # global z of strip plane 0
    y_base = iy * ty - oy
    x_base = ix * tx - ox
    groups = group_by_leading(taps)

    def valid(zg, y0=0, x0=0, ny=sy, nx=sx):
        """Dirichlet validity of the in-plane window at (y0, x0) of a
        plane at global z ``zg``."""
        yg = jax.lax.broadcasted_iota(jnp.int32, (ny, nx), 0) + y_base + y0
        xg = jax.lax.broadcasted_iota(jnp.int32, (ny, nx), 1) + x_base + x0
        return ((zg >= 0) & (zg < zdim) & (yg >= 0) & (yg < ydim)
                & (xg >= 0) & (xg < xdim))

    def zero_slot(k, carry):
        q[k // ring, k % ring] = jnp.zeros(q.shape[2:], q.dtype)
        return carry
    jax.lax.fori_loop(0, t * ring, zero_slot, 0)

    ylen = (ry, ty, ry) if tiled_y else (sy,)
    xlen = (rx, tx, rx) if tiled_x else (sx,)
    yoff = (0, ry, ry + ty) if tiled_y else (0,)
    xoff = (0, rx, rx + tx) if tiled_x else (0,)

    def stage(p):
        """Enqueue strip plane ``p`` (time 0) from its z view."""
        slot = p & (ring - 1)
        for zi, lo, n in ((0, 0, halo), (1, halo, zc), (2, halo + zc, halo)):
            @pl.when((p >= lo) & (p < lo + n))
            def _(zi=zi, lo=lo):
                for yi in range(nyk):
                    for xi in range(nxk):
                        v = refs[(zi * nyk + yi) * nxk + xi][p - lo]
                        y0, x0 = yoff[yi], xoff[xi]
                        ok = valid(z_base + p, y0, x0, ylen[yi], xlen[xi])
                        q[0, slot, pl.ds(m + y0, ylen[yi]),
                          pl.ds(x0, xlen[xi])] = jnp.where(ok, v, 0.0)

    def plane(p, carry):
        stage(p)

        def advance(s, carry):
            """Time-``s`` plane ``p − s·rad`` from queue ``s − 1``."""
            qz = p - s * rad
            acc = None
            for dz, taps2d in groups:
                v = q[s - 1, (qz + dz) & (ring - 1)]
                c = apply_taps_rows(v, taps2d, sy)
                acc = c if acc is None else acc + c
            acc = jnp.where(valid(z_base + qz), acc, 0.0)

            @pl.when(s < t)
            def _():
                q[s, qz & (ring - 1), pl.ds(m, sy)] = acc

            @pl.when((s == t) & (qz >= halo) & (qz < halo + zc))
            def _():
                out_ref[qz - halo] = acc[oy:oy + by, ox:ox + bx].astype(
                    out_ref.dtype)
            return carry
        jax.lax.fori_loop(1, t + 1, advance, 0)
        return carry
    jax.lax.fori_loop(0, zc + 2 * halo, plane, 0)


def padded_shape_3d(spec: StencilSpec, t: int, shape: tuple[int, int, int],
                    *, zc: int = 16, ty: int | None = None,
                    tx: int | None = None,
                    aligned: bool = False) -> tuple[int, int, int]:
    """Padded layout a 3-D launch uses (see ``launch_geometry_3d``)."""
    return launch_geometry_3d(spec, t, shape, zc=zc, ty=ty, tx=tx,
                              aligned=aligned)["padded"]


@functools.partial(jax.jit, static_argnames=(
    "spec", "t", "zdim", "ydim", "xdim", "zc", "ty", "tx", "lazy_batch",
    "interpret", "aligned"))
def ebisu3d_padded(xpad: jnp.ndarray, spec: StencilSpec, t: int, *,
                   zdim: int, ydim: int, xdim: int, zc: int = 16,
                   ty: int | None = None, tx: int | None = None,
                   lazy_batch: int | None = None,
                   interpret: bool = True,
                   aligned: bool | None = None) -> jnp.ndarray:
    """Padded-layout sweep: ``xpad`` is the ``padded_shape_3d`` layout with
    zeros outside the domain at the origin; returns the same layout
    (out-of-domain cells again zero — DESIGN.md §9.3).

    ``aligned`` selects the Mosaic layout and body; a launch lowered by
    Mosaic (``interpret=False``) is always aligned, and the interpreter
    runs the aligned body when asked (how the CPU tests check it).  The
    aligned body streams one plane per loop iteration, so ``lazy_batch``
    shapes only the interpreter's schedule."""
    assert spec.ndim == 3
    aligned = bool(aligned) or not interpret
    rad = spec.radius
    zc, halo = chunk_geometry(spec, t, zc)
    ay, ax = _aligns(aligned)
    ry, rx = _pad_to(halo, ay), _pad_to(halo, ax)
    ty_r, tiled_y = xy_tile(spec, t, ydim, ty, ay)
    tx_r, tiled_x = xy_tile(spec, t, xdim, tx, ax)
    kz = zc // halo
    batch, w, _ = stream_schedule(zc, halo, rad,
                                  lazy_batch if lazy_batch else zc)

    zp, yp, xp = xpad.shape
    assert (zp, yp, xp) == padded_shape_3d(spec, t, (zdim, ydim, xdim),
                                           zc=zc, ty=ty, tx=tx,
                                           aligned=aligned), xpad.shape
    grid = (zp // zc,
            yp // ty_r if tiled_y else 1,
            xp // tx_r if tiled_x else 1)
    nsub_z, nsub_y, nsub_x = zp // halo, yp // ry if tiled_y else 1, \
        xp // rx if tiled_x else 1

    # Per-axis view kinds: rim sub-block before the body, the body, rim
    # after.  Clamped rim ids at the domain edges deliver in-array data
    # whose strip-global coordinates are out of domain — zeroed by the
    # validity masks (DESIGN.md §8.4).
    def z_idx(kind):
        return {"top": lambda i: jnp.maximum(i * kz - 1, 0),
                "mid": lambda i: i,
                "bot": lambda i: jnp.minimum((i + 1) * kz, nsub_z - 1)}[kind]

    def plane_idx(kind, k_blocks, nsub):
        return {"top": lambda j: jnp.maximum(j * k_blocks - 1, 0),
                "mid": lambda j: j,
                "bot": lambda j: jnp.minimum((j + 1) * k_blocks,
                                             nsub - 1)}[kind]

    zkinds = ("top", "mid", "bot")
    ykinds = ("top", "mid", "bot") if tiled_y else ("mid",)
    xkinds = ("top", "mid", "bot") if tiled_x else ("mid",)
    zlen = {"top": halo, "mid": zc, "bot": halo}
    ylen = {"top": ry, "mid": ty_r if tiled_y else yp, "bot": ry}
    xlen = {"top": rx, "mid": tx_r if tiled_x else xp, "bot": rx}

    in_specs = []
    for zk in zkinds:
        fz = z_idx(zk)
        for yk in ykinds:
            fy = (plane_idx(yk, ty_r // ry, nsub_y) if tiled_y
                  else (lambda j: 0))
            for xk in xkinds:
                fx = (plane_idx(xk, tx_r // rx, nsub_x) if tiled_x
                      else (lambda k: 0))
                in_specs.append(pl.BlockSpec(
                    (zlen[zk], ylen[yk], xlen[xk]),
                    lambda i, j, k, fz=fz, fy=fy, fx=fx:
                    (fz(i), fy(j), fx(k))))

    out_block = (zc, ty_r if tiled_y else yp, tx_r if tiled_x else xp)
    out_idx = (lambda i, j, k:
               (i, j if tiled_y else 0, k if tiled_x else 0))

    params = {}
    if aligned:
        sy = ty_r + 2 * ry if tiled_y else yp
        sx = tx_r + 2 * rx if tiled_x else xp
        ring = next_pow2(2 * rad + 1)
        kern = functools.partial(
            _stream_kernel_aligned, taps=spec.taps, t=t, rad=rad, zc=zc,
            halo=halo, zdim=zdim, ydim=ydim, xdim=xdim, ty=ty_r, tx=tx_r,
            ry=ry, rx=rx, nyk=len(ykinds), nxk=len(xkinds))
        scratch = pltpu.VMEM((t, ring, sy + 2 * MARGIN, sx), xpad.dtype)
        # the double-buffered in/out blocks of the Pallas pipeline plus
        # the queue rings (DESIGN.md §2: the limit's cap comes from the
        # compile rehearsal)
        claim = (2 * ((zc + 2 * halo) * sy * sx + zc * out_block[1]
                      * out_block[2])
                 + t * ring * (sy + 2 * MARGIN) * sx) * xpad.dtype.itemsize
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=vmem_limit(claim))
    else:
        kern = functools.partial(
            _stream_kernel, taps=spec.taps, t=t, rad=rad, zc=zc, halo=halo,
            batch=batch, zdim=zdim, ydim=ydim, xdim=xdim, ty=ty_r, tx=tx_r,
            nyk=len(ykinds), nxk=len(xkinds))
        # exact-extent shifting windows: the interpreter's ref writes are
        # functional whole-buffer copies, so pad lanes would only add
        # copy cost (DESIGN.md §9.2)
        sy = ty_r + 2 * halo if tiled_y else ydim
        sx = tx_r + 2 * halo if tiled_x else xdim
        scratch = pltpu.VMEM((t, w, sy, sx), xpad.dtype)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, out_idx),
        out_shape=jax.ShapeDtypeStruct((zp, yp, xp), xpad.dtype),
        scratch_shapes=[scratch],
        interpret=interpret,
        **params,
    )(*([xpad] * len(in_specs)))


@functools.partial(jax.jit, static_argnames=("spec", "t", "zc", "ty", "tx",
                                             "lazy_batch", "interpret",
                                             "aligned",
                                             "boundary", "compute_dtype"))
def ebisu3d(x: jnp.ndarray, spec: StencilSpec, t: int, *, zc: int = 16,
            ty: int | None = None, tx: int | None = None,
            lazy_batch: int | None = None,
            interpret: bool = True, aligned: bool | None = None,
            boundary=None, compute_dtype=None) -> jnp.ndarray:
    """Apply ``t`` temporally-blocked steps of a 3-D ``spec`` via z-streaming.

    ``boundary`` (default: zero Dirichlet) is resolved by reduction to
    the zero-Dirichlet core — the affine closure for dirichlet(v),
    per-sweep deep-halo ghost pinning for periodic/reflect
    (``taps.with_boundary``).  ``compute_dtype`` (default float32) is the
    dtype of the padded compute buffer and the VMEM streaming windows.
    """
    assert spec.ndim == 3
    if not is_zero_dirichlet(boundary):
        check_boundary(spec.taps, boundary, t)
        return with_boundary(
            x, 3, spec.halo(t), boundary,
            lambda v: ebisu3d(v, spec, t, zc=zc, ty=ty, tx=tx,
                              lazy_batch=lazy_batch,
                              interpret=interpret, aligned=aligned,
                              compute_dtype=compute_dtype),
            taps=spec.taps, t=t)
    cdtype = jnp.dtype(compute_dtype) if compute_dtype else jnp.float32
    zdim, ydim, xdim = x.shape
    aligned = bool(aligned) or not interpret
    zp, yp, xp = padded_shape_3d(spec, t, x.shape, zc=zc, ty=ty, tx=tx,
                                 aligned=aligned)
    xpad = jnp.zeros((zp, yp, xp), cdtype).at[
        :zdim, :ydim, :xdim].set(x.astype(cdtype))
    out = ebisu3d_padded(xpad, spec, t, zdim=zdim, ydim=ydim, xdim=xdim,
                         zc=zc, ty=ty, tx=tx, lazy_batch=lazy_batch,
                         interpret=interpret, aligned=aligned)
    return out[:zdim, :ydim, :xdim].astype(x.dtype)
