"""Unified slice-based tap engine — the one stencil-application core.

Every stencil application in the repo (the 2-D strip kernel, the 3-D
streamer, the sharded per-shard trapezoid chain of
``repro.api.sharded`` — DESIGN.md §12.2 — and the pure-jnp oracle) goes
through this module, so the blocked kernels and the reference they are
validated against share one numerical definition of "apply the taps"
(see DESIGN.md §8).

Semantics: *zero-fill* shifts.  ``apply_taps`` treats everything outside
the array extent as 0 — a static slice of a zero-padded buffer, never
``jnp.roll``.  No wrap-around means no per-step wrap remask: the only
masking a kernel still needs is the Dirichlet boundary of the *domain*
(which can sit strictly inside a padded strip), and that collapses to a
single {0,1} mask built once at strip assembly and applied as one
multiply per step (DESIGN.md §8.2).

Four application paths:

  * generic   — pad the tap axes once, then one static slice + FMA per
                tap.  Works for any tap set (box stencils).
  * star      — separable axis-wise accumulation: one 1-axis pad + 2·rad
                slices per axis plus the center term.  Slices stay
                contiguous along the untouched minor axes, which is both
                cheaper to move and what the VPU wants.
  * dz-grouped window — for the 3-D streamer: a *valid*-mode application
                along z over a ``B + 2·rad``-plane window producing ``B``
                planes, with zero-fill only in-plane.  Every z-slice is
                static, so the streamer's batched advance is one
                vectorized call per temporal step.
  * aligned rows — for kernels lowered by Mosaic (``apply_taps_rows``):
                shifts are ``pltpu.roll`` of (8, 128)-aligned values, row
                context comes from 8-row margins loaded with the rows,
                and a lane select zeroes what a roll wraps around.  The
                accumulation order is the star/generic order above, so
                the result matches the slice paths to rounding.

Every path also supports per-axis **valid-mode** application (``crops``):
a tap axis with ``crops[a] = c > 0`` is not zero-padded — the output
shrinks by ``c`` cells on each side and every tap reads true neighbor
values.  This is the AN5D-style trapezoid: ``chain_trapezoid`` narrows
the live region by one radius per temporal step, so step ``s`` of a
``t``-deep chain computes only the cells that can still influence the
final output (DESIGN.md §9.1) — the FLOP side of temporal blocking
shrinks with depth instead of recomputing the full haloed strip every
step.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

Taps = Sequence[tuple[tuple[int, ...], float]]


def tap_radius(taps: Taps) -> int:
    """Largest |offset| component — the pad the generic path needs."""
    return max((max(abs(o) for o in off) for off, _ in taps), default=0)


def group_by_leading(taps: Taps):
    """Group 3-D taps by dz: ``[(dz, [((dy, dx), c), ...]), ...]`` sorted.

    The dz-grouped form is what z-streaming consumes: each group is an
    in-plane (2-D) tap set contributed by one relative input plane.
    """
    groups: dict[int, list] = {}
    for off, c in taps:
        dz, rest = off[0], tuple(off[1:])
        groups.setdefault(dz, []).append((rest, c))
    return sorted((dz, tuple(ts)) for dz, ts in groups.items())


def split_star(taps: Taps, ndim: int):
    """Split a star tap set into (center_coeff, per-axis arms).

    Returns ``None`` if any tap has more than one nonzero offset component
    (i.e. the set is not a star and the axis-wise path does not apply).
    ``arms[a]`` is a list of ``(offset, coeff)`` with offset != 0 along
    tap-axis ``a``.
    """
    center = 0.0
    arms: list[list[tuple[int, float]]] = [[] for _ in range(ndim)]
    for off, c in taps:
        nz = [i for i, o in enumerate(off) if o]
        if not nz:
            center += c
        elif len(nz) == 1:
            arms[nz[0]].append((off[nz[0]], c))
        else:
            return None
    return center, arms


def apply_taps_generic(x: jnp.ndarray, taps: Taps, ndim: int,
                       crops: Sequence[int] | None = None) -> jnp.ndarray:
    """One stencil application on the last ``ndim`` axes of ``x``.

    Pads the tap axes once by the tap radius, then realizes every tap as
    a single static slice of the padded buffer.  Leading axes of ``x``
    (e.g. a batch of planes) broadcast through untouched.

    ``crops[a] = c > 0`` switches tap-axis ``a`` to *valid* mode: no
    zero-pad, the output shrinks by ``c`` on each side, and every tap
    (``|off| ≤ c``) reads true neighbor values from ``x`` itself.
    """
    rad = tap_radius(taps)
    lead = x.ndim - ndim
    crops = tuple(crops) if crops is not None else (0,) * ndim
    for a, c in enumerate(crops):
        # a valid-mode slice with |off| > crop would wrap via a negative
        # start instead of erroring — refuse it outright
        assert c == 0 or c >= max(abs(off[a]) for off, _ in taps), (a, c)
    pad = [(0, 0)] * lead + [(0, 0) if c else (rad, rad) for c in crops]
    xp = jnp.pad(x, pad) if any(p != (0, 0) for p in pad) else x
    base = [c if c else rad for c in crops]
    out_n = [n - 2 * c for n, c in zip(x.shape[lead:], crops)]
    acc = None
    for off, c in taps:
        idx = (Ellipsis,) + tuple(
            slice(b + o, b + o + n) for b, o, n in zip(base, off, out_n))
        term = xp[idx] * jnp.asarray(c, x.dtype)
        acc = term if acc is None else acc + term
    return acc


def apply_taps_star(x: jnp.ndarray, center: float,
                    arms: Sequence[Sequence[tuple[int, float]]],
                    ndim: int,
                    crops: Sequence[int] | None = None) -> jnp.ndarray:
    """Axis-wise (separable-shape) accumulation for star tap sets.

    ``crops`` has the same valid-mode semantics as in
    ``apply_taps_generic``: cropped axes shrink and read true neighbors.
    """
    lead = x.ndim - ndim
    crops = tuple(crops) if crops is not None else (0,) * ndim

    def crop_axes(exclude: int = -1):
        idx = [slice(None)] * x.ndim
        for b, cp in enumerate(crops):
            if cp and b != exclude:
                idx[lead + b] = slice(cp, x.shape[lead + b] - cp)
        return idx

    acc = x[tuple(crop_axes())] * jnp.asarray(center, x.dtype)
    for a, axis_arms in enumerate(arms):
        if not axis_arms:
            continue
        axis = lead + a
        rad = max(abs(o) for o, _ in axis_arms)
        n = x.shape[axis]
        cp = crops[a]
        assert cp == 0 or cp >= rad, (a, cp, rad)  # see apply_taps_generic
        if cp:
            xp, base, out_a = x, cp, n - 2 * cp
        else:
            pad = [(0, 0)] * x.ndim
            pad[axis] = (rad, rad)
            xp, base, out_a = jnp.pad(x, pad), rad, n
        for off, c in axis_arms:
            idx = crop_axes(exclude=a)
            idx[axis] = slice(base + off, base + off + out_a)
            acc = acc + xp[tuple(idx)] * jnp.asarray(c, x.dtype)
    return acc


# Row margin of the aligned path: one f32 sublane tile above and below.
MARGIN = 8


def _row_shift(v: jnp.ndarray, dy: int, rows: int) -> jnp.ndarray:
    """``out[i] = v[MARGIN + i + dy]`` for ``i < rows`` (``|dy| <= MARGIN``),
    from the two sublane tiles the shift touches — every slice aligned."""
    m = MARGIN
    if dy == 0:
        return v[m:m + rows]
    if dy > 0:
        u = v[m:]
        return pltpu.roll(u, u.shape[0] - dy, 0)[:rows]
    u = v[:m + rows]
    return pltpu.roll(u, -dy, 0)[m:]


def _lane_shift(u: jnp.ndarray, dx: int) -> jnp.ndarray:
    """``out[:, j] = u[:, j + dx]``, zero where ``j + dx`` leaves the row."""
    if dx == 0:
        return u
    w = u.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    ok = (col + dx >= 0) & (col + dx < w)
    return jnp.where(ok, pltpu.roll(u, (-dx) % w, 1), jnp.zeros_like(u))


def apply_taps_rows(v: jnp.ndarray, taps2d: Taps, rows: int) -> jnp.ndarray:
    """One 2-D tap application inside a Mosaic kernel.

    ``v`` is ``(MARGIN + rows + MARGIN, W)``: the ``rows`` to compute plus
    one sublane tile of row context on each side (zero where the field
    ends), ``rows`` a multiple of 8 and ``W`` of 128.  Columns are
    zero-filled at the row ends.  Returns the ``(rows, W)`` result, with
    the terms summed in the order of ``apply_taps_star`` (star sets) or
    ``apply_taps_generic``.
    """
    dt = v.dtype

    def shifted(dy: int, dx: int) -> jnp.ndarray:
        return _lane_shift(_row_shift(v, dy, rows), dx)

    star = split_star(taps2d, 2)
    if star is not None:
        center, arms = star
        acc = shifted(0, 0) * jnp.asarray(center, dt)
        for a, axis_arms in enumerate(arms):
            for off, c in axis_arms:
                term = shifted(off, 0) if a == 0 else shifted(0, off)
                acc = acc + term * jnp.asarray(c, dt)
        return acc
    acc = None
    for (dy, dx), c in taps2d:
        term = shifted(dy, dx) * jnp.asarray(c, dt)
        acc = term if acc is None else acc + term
    return acc


class TapEngine:
    """A tap set compiled to its cheapest application path.

    ``step(x, mask)`` applies one stencil step to the last ``ndim`` axes
    of ``x`` with zero-fill shifts, then multiplies by ``mask`` (the
    one-time Dirichlet boundary mask — pass ``None`` only when the array
    edge *is* the domain boundary on every side).
    """

    def __init__(self, taps: Taps, ndim: int):
        self.taps = tuple(taps)
        self.ndim = ndim
        self.radius = tap_radius(taps)
        self._star = split_star(taps, ndim)
        self.groups = group_by_leading(taps) if ndim == 3 else None

    def step(self, x: jnp.ndarray, mask: jnp.ndarray | None = None,
             crops: Sequence[int] | None = None):
        if self._star is not None:
            center, arms = self._star
            out = apply_taps_star(x, center, arms, self.ndim, crops)
        else:
            out = apply_taps_generic(x, self.taps, self.ndim, crops)
        return out if mask is None else out * mask

    def chain(self, x: jnp.ndarray, t: int,
              mask: jnp.ndarray | None = None) -> jnp.ndarray:
        """``t`` fused steps, intermediates carried as pure values."""
        for _ in range(t):
            x = self.step(x, mask)
        return x

    def chain_trapezoid(self, x: jnp.ndarray, t: int,
                        axes: Sequence[int] = (0,),
                        post=None) -> jnp.ndarray:
        """``t`` valid-mode steps, shrinking ``axes`` by one radius each.

        Step ``s`` computes only the ``n − 2·s·rad`` live extent along
        each narrowed tap axis — the cells whose value can still reach
        the final output — using true neighbor context instead of a
        zero-fill edge (DESIGN.md §9.1).  ``post(v, s)`` (optional) is
        applied after each step; kernels use it to re-pin the Dirichlet
        domain boundary where the strip actually meets it.

        Interior equivalence: for cells at distance ≥ ``t·rad`` from the
        narrowed edges, the result equals ``chain(x, t)`` cropped by
        ``t·rad`` along ``axes`` (boundary effects travel one radius per
        step, so those cells never see the edge).
        """
        crops = tuple(self.radius if a in axes else 0
                      for a in range(self.ndim))
        for s in range(1, t + 1):
            x = self.step(x, crops=crops)
            if post is not None:
                x = post(x, s)
        return x

    # ------------------------------------------------- 3-D streaming ----
    def window_step(self, window: jnp.ndarray, batch: int,
                    mask: jnp.ndarray | None = None,
                    inplane_crops: tuple[int, int] = (0, 0)) -> jnp.ndarray:
        """Advance one temporal step over a plane window (3-D only).

        ``window`` is ``(B + 2·rad, Y, X)`` planes of time ``s``; the
        result is the ``B`` planes of time ``s+1`` they determine
        (*valid* along z — no zero-fill; the caller's shifting buffers
        provide the z context).  In-plane shifts are zero-filled, unless
        ``inplane_crops = (cy, cx)`` requests valid-mode narrowing there
        too (XY-tiled streaming: the tile's fetched y/x halo provides
        true context and the live region shrinks one radius per step —
        DESIGN.md §9.1).  Every z-slice offset is static, so each dz
        group is one vectorized 2-D application over a ``(B, Y, X)``
        block.
        """
        assert self.groups is not None, "window_step is for 3-D tap sets"
        rad = self.radius
        assert window.shape[0] == batch + 2 * rad
        cy, cx = inplane_crops
        acc = None
        for dz, taps2d in self.groups:
            block = window[rad + dz:rad + dz + batch]
            if len(taps2d) == 1 and taps2d[0][0] == (0, 0):
                iy = slice(cy, block.shape[1] - cy) if cy else slice(None)
                ix = slice(cx, block.shape[2] - cx) if cx else slice(None)
                contrib = (block[:, iy, ix]
                           * jnp.asarray(taps2d[0][1], window.dtype))
            else:
                star = split_star(taps2d, 2)
                if star is not None:
                    contrib = apply_taps_star(block, star[0], star[1], 2,
                                              crops=(cy, cx))
                else:
                    contrib = apply_taps_generic(block, taps2d, 2,
                                                 crops=(cy, cx))
            acc = contrib if acc is None else acc + contrib
        return acc if mask is None else acc * mask


@functools.lru_cache(maxsize=None)
def engine_for(taps: Taps, ndim: int) -> TapEngine:
    """Memoized engine per (taps, ndim) — specs are hashable frozen tuples."""
    return TapEngine(taps, ndim)


# ------------------------------------------------------------ boundaries ----
# The engine's zero-fill slicing realizes exactly one boundary condition:
# zero Dirichlet.  Everything else is reduced to it here, shared by the
# Pallas kernels and the oracle (the ``Boundary`` objects handed in are
# duck-typed: anything with ``.kind``/``.value`` — see repro.api.boundary).

def is_zero_dirichlet(boundary) -> bool:
    return (boundary is None
            or (boundary.kind == "dirichlet" and boundary.value == 0.0))


def tap_sum(taps: Taps) -> float:
    """Sum of tap coefficients ``s`` — the contraction factor of the affine
    closure: one true Dirichlet(v) step satisfies ``u_1 = Z(u_0 − v) + v·s``
    exactly for ANY ``s`` (DESIGN.md §11.3)."""
    return sum(c for _, c in taps)


def check_boundary(taps: Taps, boundary, t: int | None = None) -> None:
    """Raise ``ValueError`` when a ``t``-step fused chain of ``taps``
    cannot run under ``boundary`` through the zero-Dirichlet reductions
    below (``t=None``: depth unknown — require the depth-independent
    closure).

    * dirichlet(v≠0) runs through the affine closure
      ``u_t = Z_t(u_0 − v) + v·s^t`` (``s`` = tap sum), which is exact
      iff ``s == 1`` (the classic constant shift, any depth) or ``t == 1``
      (a single step — chains of depth-1 sweeps re-apply the shift every
      sweep).  For ``s ≠ 1`` at ``t ≥ 2`` the correction term
      ``v·Σ_k s^{t-1-k}(s·Z^k(1) − Z^{k+1}(1))`` is a *field* supported on
      the ``t·rad`` boundary band, not a constant — no pre/post shift of a
      fused chain can absorb it, so we refuse with the fixes spelled out.
    * reflect needs per-axis mirror symmetry of the tap set: only then is
      the mirror extension preserved by evolution, making the one-time
      deep-halo ghost fill equivalent to re-mirroring every step.
    * neumann(flux) fills ghosts by the face-mirror ``ghost(-k) = u(k-1)
      + k·flux`` (zero normal derivative for flux = 0).  A depth-1 chain
      refills the ghosts every step — exact for ANY taps and any flux.
      Deeper fused chains fill once per sweep, which is exact only when
      the tap set is mirror-symmetric per axis (so the symmetric
      extension evolves as the mirror of the interior) AND ``flux == 0``
      (one step moves a kinked flux ramp off the ``ghost(-k) = u(k-1) +
      k·flux`` relation by ``-a·flux`` at the face for arm weight ``a``
      — no tap sum fixes it), so other combinations are refused with the
      fixes spelled out.
    """
    if is_zero_dirichlet(boundary) or boundary.kind == "periodic":
        return
    if boundary.kind == "neumann":
        if t == 1:
            return                    # ghosts refilled per step: exact
        mirror = _mirror_defect(taps)
        if mirror is not None:
            off, c, a = mirror
            raise ValueError(
                f"neumann boundary at chain depth "
                f"t={'unknown' if t is None else t} needs a "
                f"mirror-symmetric tap set (the one-fill-per-sweep "
                f"symmetric extension must evolve as the mirror of the "
                f"interior); tap {off} (coeff {c:g}) has no axis-{a} "
                "mirror.  Fix: compile with t=1 (ghosts re-pinned every "
                "step, exact for any taps), or symmetrize the taps")
        if boundary.value != 0.0:
            raise ValueError(
                f"neumann(flux={boundary.value:g}) with a fused chain "
                f"t={'unknown' if t is None else t} steps deep: the "
                "constant-flux ghost ramp is only consistent under "
                "per-step refills (one stencil application bends the "
                "ramp at the face).  Fix: compile with t=1, or use "
                "neumann() with zero flux, which is exact at any depth "
                "for mirror-symmetric taps")
        return
    if boundary.kind == "dirichlet":
        s = tap_sum(taps)
        if abs(s - 1.0) > 1e-6 and t != 1:
            raise ValueError(
                f"dirichlet({boundary.value:g}) with taps summing to "
                f"s={s:.6g}: the affine closure u_t = Z_t(u - v) + v*s^t "
                f"is exact only for s == 1 or single-step sweeps, and this "
                f"chain is t={'unknown' if t is None else t} steps deep. "
                "Fix: compile with t=1 (exact, chained per sweep), "
                "normalize the taps to sum 1 "
                "(define_stencil(..., normalize=True)), or use "
                "dirichlet(0)/periodic, which are exact for any tap sum")
        return
    if boundary.kind == "reflect":
        mirror = _mirror_defect(taps)
        if mirror is not None:
            off, c, a = mirror
            raise ValueError(
                f"reflect boundary needs a mirror-symmetric tap set; "
                f"tap {off} (coeff {c:g}) has no axis-{a} mirror")
        return
    raise ValueError(f"unknown boundary kind {boundary.kind!r}")


def _mirror_defect(taps: Taps):
    """First tap breaking per-axis mirror symmetry as ``(off, coeff,
    axis)``, or ``None`` for a symmetric set (reflect/neumann need this
    symmetry for one-fill-per-sweep ghost pinning)."""
    coeff = dict(taps)
    for off, c in taps:
        for a in range(len(off)):
            m = tuple(-o if i == a else o for i, o in enumerate(off))
            if abs(coeff.get(m, 0.0) - c) > 1e-9:
                return off, c, a
    return None


def ghost_extend(x: jnp.ndarray, ndim: int, halo: int,
                 boundary) -> jnp.ndarray:
    """Extend the last ``ndim`` axes of ``x`` by ``halo`` ghost cells per
    side, filled by the boundary rule (constant / wrap / mirror /
    flux-mirror).  Leading axes (e.g. a batch) pass through unpadded.

    neumann(flux): the face-mirror ``ghost(-k) = u(k-1) + k·flux`` per
    axis — ``jnp.pad mode='symmetric'`` plus a linear ramp of slope
    ``flux`` over the ghost distance, so the outward normal derivative
    at every domain face is ``flux`` (zero-flux insulation for the
    default 0).  Corners add the per-axis ramps (the separable
    convention the oracle tests pin down)."""
    pad = [(0, 0)] * (x.ndim - ndim) + [(halo, halo)] * ndim
    if boundary.kind == "dirichlet":
        return jnp.pad(x, pad, constant_values=boundary.value)
    if boundary.kind == "neumann":
        xe = jnp.pad(x, pad, mode="symmetric")
        if boundary.value != 0.0:
            for a in range(ndim):
                axis = x.ndim - ndim + a
                i = jnp.arange(xe.shape[axis])
                n = x.shape[axis]
                dist = jnp.maximum(jnp.maximum(halo - i, i - (halo + n - 1)),
                                   0)
                shape = [1] * xe.ndim
                shape[axis] = xe.shape[axis]
                xe = xe + (dist.astype(xe.dtype)
                           * jnp.asarray(boundary.value, xe.dtype)
                           ).reshape(shape)
        return xe
    mode = {"periodic": "wrap", "reflect": "reflect"}[boundary.kind]
    return jnp.pad(x, pad, mode=mode)


def with_boundary(x: jnp.ndarray, ndim: int, halo: int, boundary, core,
                  *, taps: Taps | None = None, t: int = 1):
    """Run ``core`` — a zero-Dirichlet ``t``-step map over the last
    ``ndim`` axes — under ``boundary``, where ``halo`` is the ``t·rad``
    reach of the chain ``core`` applies.

    dirichlet(v): the affine closure ``core(x − v) + v·s^t`` (``s`` = tap
    sum; no extra traffic at all) — the constant shift when ``s = 1``,
    exact for any ``s`` when ``t = 1`` (``check_boundary`` enforces one of
    the two; pass ``taps`` so ``s`` is known — omitting them assumes a
    normalized set).
    periodic/reflect: deep-halo ghost pinning — extend by ``halo``
    boundary-true cells, run ``core`` on the extended domain (its
    zero-fill corruption stays inside the ghost ring for ``t`` steps),
    crop the domain back out.  Caller is responsible for
    ``check_boundary`` having passed.
    """
    if is_zero_dirichlet(boundary):
        return core(x)
    if boundary.kind == "dirichlet":
        v = jnp.asarray(boundary.value, x.dtype)
        scale = tap_sum(taps) ** t if taps is not None else 1.0
        return core(x - v) + v * jnp.asarray(scale, x.dtype)
    xe = ghost_extend(x, ndim, halo, boundary)
    ye = core(xe)
    crop = (Ellipsis,) + tuple(slice(halo, halo + n)
                               for n in x.shape[x.ndim - ndim:])
    return ye[crop]
