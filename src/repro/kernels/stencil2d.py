"""EBISU-2D Pallas kernel: temporally-blocked strip device-tiles.

TPU mapping of the paper's 2-D scheme (§4.1, §6.3.1, §6.4.1):

  * Each Pallas grid step is a *device tile*: one full-width strip of
    ``bh`` output rows, resident in VMEM while ``t`` time steps are applied
    ("one tile at a time" — the TPU grid is sequential, so low occupancy is
    the native execution model).
  * **Halo-exact fetching**: the input is re-blocked at halo granularity.
    A grid step reads its ``bh`` body rows plus one ``halo``-row sub-block
    above and below (``HALO = t·rad``), so input traffic per strip is
    ``bh + 2·halo`` rows — not the ``3·bh`` of fetching whole neighbor
    blocks to use only their rims.  ``bh`` is rounded up to a multiple of
    ``halo`` so the rim sub-blocks are block-aligned (Pallas blocks cannot
    overlap; DESIGN.md §8.4).
  * Taps are applied by the shared slice-based engine
    (``repro.kernels.taps``): zero-fill static slices, no ``jnp.roll`` —
    no wrap-around, so the only masking left is the Dirichlet domain
    boundary, built **once** per strip and applied as a single multiply
    per step (DESIGN.md §8.1-2).
  * ``mode='fused'`` chains the ``t`` steps as pure jnp values — Mosaic
    keeps intermediates in VREGs/VMEM without explicit round-trips: the
    TPU realization of *redundant register streaming* (§4.3.3).  The
    chain is **trapezoid-narrowed** (AN5D-style): step ``s`` computes
    only the ``sh − 2·s·rad`` rows that can still influence the strip's
    output, using true neighbor context (valid-mode rows), and the
    Dirichlet row mask is re-pinned per step only when the strip
    actually meets the domain boundary — interior strips run mask-free
    (DESIGN.md §9.1).
  * ``mode='scratch'`` ping-pongs two explicit VMEM scratch buffers — the
    paper's double-buffering, i.e. lazy streaming with a single queue
    (§4.3.2); kept for the Fig-9-style ablation.
  * **Aligned launches** (``aligned=True``; always when Mosaic lowers
    the kernel): every block obeys the TPU's (8, 128)
    tiling.  The rim sub-blocks are ``rim = round8(halo)`` rows — the
    strip fetches ``bh + 2·rim`` rows and only the innermost ``halo`` of
    each rim reaches the output — and ``bh`` is a multiple of ``rim``.
    The strip is staged into a ping-pong pair of VMEM buffers with one
    zero sublane tile of margin per side, and each step walks it in
    8-row chunks under ``lax.fori_loop`` with ``pltpu.roll`` shifts
    (``taps.apply_taps_rows``).  The generated code is one chunk body,
    whatever ``t`` and the strip size, so the compile takes seconds;
    both modes run this body (DESIGN.md §8.4, §8.6).

Boundary semantics: zero outside the domain at every step (the oracle's
contract).  The domain sits at rows ``[0, height)`` × cols ``[0, width)``
of the padded compute array, so the top/left Dirichlet boundaries coincide
with the zero-fill slicing edge for free; bottom/right (and the strip's
clamped rim sub-blocks at the domain edges) are zeroed by the strip mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stencil_spec import StencilSpec
from repro.kernels.taps import (MARGIN, apply_taps_rows, check_boundary,
                                engine_for, is_zero_dirichlet, with_boundary)

# Largest vmem_limit_bytes a launch asks for (see ``vmem_limit``).
VMEM_LIMIT_CAP = 128 << 20


def _strip_kernel(top_ref, mid_ref, bot_ref, out_ref, *scratch,
                  taps, t: int, bh: int, halo: int,
                  height: int, width: int, mode: str):
    i = pl.program_id(0)
    sh = bh + 2 * halo
    wp = mid_ref.shape[1]
    engine = engine_for(taps, 2)
    rad = engine.radius
    # compute dtype policy: the kernel computes in the dtype of the padded
    # buffer it was handed — the program layer decides that dtype
    cdtype = mid_ref.dtype

    # --- one-time Dirichlet boundary mask (DESIGN.md §8.2).  Columns need no
    # mask: the strip is cropped to the true domain width, so the zero-fill
    # slicing edge *is* the left/right Dirichlet boundary.  Rows keep a
    # (sh, 1) mask — the top/bottom domain boundary moves with the strip.
    row0 = i * bh - halo
    rows = jax.lax.broadcasted_iota(jnp.int32, (sh, 1), 0) + row0
    mask = ((rows >= 0) & (rows < height)).astype(cdtype)

    # --- assemble the haloed strip from the halo-exact views ----------------
    vals = jnp.concatenate(
        [top_ref[...], mid_ref[...], bot_ref[...]], axis=0
    )[:, :width] * mask

    def emit(body: jnp.ndarray) -> None:
        out_ref[...] = jnp.pad(body, ((0, 0), (0, wp - width))
                               ).astype(out_ref.dtype)

    if mode == "fused":
        # Trapezoid narrowing (DESIGN.md §9.1): step s computes only rows
        # [s·rad, sh − s·rad) in valid mode; after t steps exactly the bh
        # body rows remain.  The Dirichlet row boundary is re-pinned per
        # step only on strips that meet it — interior strips (the whole
        # haloed extent inside [0, height)) run mask-free.
        interior = (row0 >= 0) & (row0 + sh <= height)

        def repin(v: jnp.ndarray, s: int) -> jnp.ndarray:
            n = sh - 2 * s * rad

            def masked(u):
                rr = (jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
                      + row0 + s * rad)
                return u * ((rr >= 0) & (rr < height)).astype(u.dtype)

            return jax.lax.cond(interior, lambda u: u, masked, v)

        emit(engine.chain_trapezoid(vals, t, axes=(0,), post=repin))
        return

    # --- 'scratch': explicit VMEM double-buffering (paper's lazy streaming /
    # double-buffer special case) --------------------------------------------
    buf0, buf1 = scratch
    buf0[...] = vals
    for s in range(t):
        src, dst = (buf0, buf1) if s % 2 == 0 else (buf1, buf0)
        dst[...] = engine.step(src[...], mask)
    final = buf1[...] if t % 2 == 1 else buf0[...]
    emit(final[halo:halo + bh, :])


def _strip_kernel_aligned(top_ref, mid_ref, bot_ref, out_ref, buf, *,
                          taps, t: int, bh: int, rim: int,
                          height: int, width: int):
    """The Mosaic body: ``buf`` is ``(2, MARGIN + sh + MARGIN, wp)``, a
    ping-pong pair holding the strip between zero row margins."""
    i = pl.program_id(0)
    sh = bh + 2 * rim
    wp = mid_ref.shape[1]
    m = MARGIN
    row0 = i * bh - rim                 # global row of strip row 0

    zero = jnp.zeros((2, m, wp), buf.dtype)
    buf[:, 0:m] = zero
    buf[:, m + sh:] = zero

    def domain(r):
        """Dirichlet validity of strip rows [r, r + 8), all columns."""
        rows = jax.lax.broadcasted_iota(jnp.int32, (m, wp), 0) + row0 + r
        cols = jax.lax.broadcasted_iota(jnp.int32, (m, wp), 1)
        return (rows >= 0) & (rows < height) & (cols < width)

    def chunks(n_rows, body):
        def step(c, carry):
            body(pl.multiple_of(c * m, m))
            return carry
        jax.lax.fori_loop(0, n_rows // m, step, 0)

    # stage the strip (clamped rims at the domain edges are zeroed here)
    for ref, first in ((top_ref, 0), (mid_ref, rim), (bot_ref, rim + bh)):
        def stage(r, ref=ref, first=first):
            buf[0, pl.ds(m + first + r, m), :] = jnp.where(
                domain(first + r), ref[pl.ds(r, m), :], 0.0)
        chunks(ref.shape[0], stage)

    def sweep_step(s, carry):
        src, dst = (s - 1) % 2, s % 2

        def rows(r):
            v = buf[src, pl.ds(r, 3 * m), :]      # strip rows r-8..r+16
            acc = apply_taps_rows(v, taps, m)
            buf[dst, pl.ds(m + r, m), :] = jnp.where(domain(r), acc, 0.0)
        chunks(sh, rows)
        return carry
    jax.lax.fori_loop(1, t + 1, sweep_step, 0)

    def emit(r):
        out_ref[pl.ds(r, m), :] = buf[t % 2, pl.ds(m + rim + r, m), :]
    chunks(bh, emit)


def _pad_to(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def strip_geometry(spec: StencilSpec, t: int, bh: int,
                   aligned: bool = False) -> tuple[int, int]:
    """Resolve the (bh, rim) a 2-D launch will actually use.

    ``rim`` is the row count of each rim sub-block: the halo itself, or
    the halo rounded up to the 8-row sublane tile on an aligned launch.
    ``bh`` is raised to at least one rim and rounded up to a multiple of
    it so the rim sub-blocks of the halo-exact fetch are block-aligned.
    """
    halo = spec.halo(t)
    rim = _pad_to(halo, 8) if aligned else halo
    bh = max(bh, rim)
    return _pad_to(bh, rim), rim


def input_rows_per_strip(spec: StencilSpec, t: int, bh: int,
                         aligned: bool = False) -> tuple[int, int]:
    """Modeled input traffic: (rows fetched per strip, strip body rows).

    The BlockSpecs fetch exactly ``bh + 2·rim`` rows per ``bh``-row
    strip, i.e. each input element is read at most ``1 + 2·rim/bh`` times
    per sweep of ``t`` steps (``rim = halo`` unless ``aligned``).
    """
    bh, rim = strip_geometry(spec, t, bh, aligned)
    return bh + 2 * rim, bh


def padded_shape_2d(spec: StencilSpec, t: int, bh: int,
                    height: int, width: int,
                    aligned: bool = False) -> tuple[int, int]:
    """Padded layout a 2-D launch uses: rows to a strip multiple, cols to 128."""
    bh, _ = strip_geometry(spec, t, bh, aligned)
    return _pad_to(height, bh), _pad_to(width, 128)


def vmem_limit(claim: int) -> int:
    """The ``vmem_limit_bytes`` for a kernel whose blocks and scratch
    claim ``claim`` bytes: a quarter more for Mosaic's own temporaries,
    at least 32 MiB, at most ``VMEM_LIMIT_CAP``."""
    return int(min(VMEM_LIMIT_CAP, max(32 << 20, claim + claim // 4)))


@functools.partial(jax.jit, static_argnames=("spec", "t", "height", "width",
                                             "bh", "mode", "interpret",
                                             "aligned"))
def ebisu2d_padded(xp: jnp.ndarray, spec: StencilSpec, t: int, *,
                   height: int, width: int, bh: int = 128,
                   mode: str = "fused", interpret: bool = True,
                   aligned: bool | None = None) -> jnp.ndarray:
    """Padded-layout sweep: ``xp`` is ``(hp, wp)`` with zeros outside the
    ``height × width`` domain at the origin; returns the same layout
    (out-of-domain cells again zero — DESIGN.md §9.3).  This is the
    multi-sweep executor's hot path: chaining sweeps through it pays no
    per-sweep pad/crop.

    ``aligned`` selects the Mosaic layout and body.  A launch lowered by
    Mosaic (``interpret=False``) is always aligned; the interpreter runs
    the aligned body when asked, which is how the CPU tests check the
    body the chip runs."""
    assert spec.ndim == 2
    aligned = bool(aligned) or not interpret
    bh, rim = strip_geometry(spec, t, bh, aligned)
    sh = bh + 2 * rim
    k = bh // rim                       # rim sub-blocks per strip body

    hp, wp = xp.shape
    assert hp % bh == 0 and wp % 128 == 0, (xp.shape, bh)
    grid = hp // bh
    nsub = hp // rim

    # Halo-exact index maps: the rim views are (rim, wp) sub-blocks — the
    # last sub-block of strip i-1 and the first of strip i+1.  Clamped ids at
    # the domain edges deliver garbage rows that the strip mask zeroes.
    def idx_top(i):
        return (jnp.maximum(i * k - 1, 0), 0)

    def idx_mid(i):
        return (i, 0)

    def idx_bot(i):
        return (jnp.minimum((i + 1) * k, nsub - 1), 0)

    params = {}
    if aligned:
        kern = functools.partial(
            _strip_kernel_aligned, taps=spec.taps, t=t, bh=bh, rim=rim,
            height=height, width=width)
        scratch_shapes = [pltpu.VMEM((2, sh + 2 * MARGIN, wp), xp.dtype)]
        # grid steps are independent ⇒ 'parallel' (§6.1).  The limit
        # covers what the launch claims: the double-buffered in/out blocks
        # of the Pallas pipeline plus the ping-pong scratch.
        claim = (2 * (sh + bh) + 2 * (sh + 2 * MARGIN)) * wp * \
            xp.dtype.itemsize
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit(claim))
    else:
        kern = functools.partial(
            _strip_kernel, taps=spec.taps, t=t, bh=bh, halo=rim,
            height=height, width=width, mode=mode)
        scratch_shapes = []
        if mode == "scratch":
            scratch_shapes = [pltpu.VMEM((sh, width), xp.dtype),
                              pltpu.VMEM((sh, width), xp.dtype)]

    return pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[pl.BlockSpec((rim, wp), idx_top),
                  pl.BlockSpec((bh, wp), idx_mid),
                  pl.BlockSpec((rim, wp), idx_bot)],
        out_specs=pl.BlockSpec((bh, wp), idx_mid),
        out_shape=jax.ShapeDtypeStruct((hp, wp), xp.dtype),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        **params,
    )(xp, xp, xp)


@functools.partial(jax.jit, static_argnames=("spec", "t", "bh", "mode",
                                             "interpret", "aligned",
                                             "boundary", "compute_dtype"))
def ebisu2d(x: jnp.ndarray, spec: StencilSpec, t: int, *, bh: int = 128,
            mode: str = "fused",
            interpret: bool = True, aligned: bool | None = None,
            boundary=None, compute_dtype=None) -> jnp.ndarray:
    """Apply ``t`` temporally-blocked steps of ``spec`` to a 2-D field.

    ``boundary`` (default: zero Dirichlet) is resolved by reduction to
    the zero-Dirichlet core: the affine closure for dirichlet(v),
    deep-halo ghost pinning (extend by ``t·rad`` boundary-true cells,
    sweep, crop) for periodic/reflect — see ``taps.with_boundary``.
    ``compute_dtype`` (default float32) is the dtype of the padded
    compute buffer — the result is cast back to ``x.dtype``.
    """
    assert spec.ndim == 2
    if not is_zero_dirichlet(boundary):
        check_boundary(spec.taps, boundary, t)
        return with_boundary(
            x, 2, spec.halo(t), boundary,
            lambda v: ebisu2d(v, spec, t, bh=bh, mode=mode,
                              interpret=interpret, aligned=aligned,
                              compute_dtype=compute_dtype),
            taps=spec.taps, t=t)
    cdtype = jnp.dtype(compute_dtype) if compute_dtype else jnp.float32
    height, width = x.shape
    aligned = bool(aligned) or not interpret
    hp, wp = padded_shape_2d(spec, t, bh, height, width, aligned)
    xp = jnp.zeros((hp, wp), cdtype).at[:height, :width].set(
        x.astype(cdtype))
    out = ebisu2d_padded(xp, spec, t, height=height, width=width, bh=bh,
                         mode=mode, interpret=interpret, aligned=aligned)
    return out[:height, :width].astype(x.dtype)
