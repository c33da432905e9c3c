"""``StencilProgram``: the compile-once front door for temporal blocking.

EBISU's pitch (paper §6) is *plan once, then drive aggressive deep
blocking tile-by-tile*.  This module is where that contract lives:
``compile_stencil`` resolves the §6 plan, the launch geometry, and the
boundary-condition execution strategy exactly once, and hands back an
immutable :class:`StencilProgram` whose runners are built and memoized
per launch signature — every other entry point in the repo
(``ops.ebisu_stencil``, ``sweep.run_sweeps``, ``ops.launch_geometry``)
is a thin shim over a program, so there is exactly ONE geometry/dispatch
resolution path.

    prog = compile_stencil(get("j3d7pt"), (256, 288, 384), t=4)
    y   = prog.run(x, T=64)          # T steps as chained zero-copy sweeps
    ys  = prog.run_batched(xs, T=64) # leading batch axis, one vmapped runner

Execution surface:

  * ``apply(x, t=None)``   — one temporally-blocked sweep.
  * ``run(x, T)``          — a ``T``-step simulation as chained sweeps;
    subsumes the zero-copy multi-sweep executor (DESIGN.md §9.3: pad
    once / crop once / dispatch once for Dirichlet boundaries, per-sweep
    ghost re-pin for periodic/reflect — DESIGN.md §10).
  * ``run_padded(xp, T)``  — the 2-D padded-layout chain with a donated
    carry (XLA ping-pongs two buffers where the backend supports it).
  * ``run_batched(xs, T=None)`` — leading batch axis via one vmapped
    padded runner (a single jitted dispatch for the whole batch).
  * ``geometry(t=None)`` / ``cost(t=None)`` / ``cache_stats()`` —
    introspection: the launch the kernels will resolve, the §5 roofline
    estimate, and the hit/miss counters of the bounded caches.

All module-global state is held in explicit bounded :class:`ProgramCache`
instances (LRU + counters + ``clear()``) — no unbounded module dicts.
Importing this module never initializes a JAX backend (checked by
``scripts/tier1.sh``): backend questions are answered at compile time,
not import time.
"""
from __future__ import annotations

import functools
import math
import threading
import warnings
from collections import OrderedDict

import jax
import jax.numpy as jnp

from repro.api.boundary import ZERO, Boundary
from repro.core import roofline as rl
from repro.core.planner import (EbisuPlan, fit_streaming_batch,
                                plan as make_plan, vmem_required_2d)
from repro.core.stencil_spec import (StencilSpec, lift_2d_to_3d,
                                     validate_spec)
from repro.kernels.stencil2d import (ebisu2d, ebisu2d_padded,
                                     padded_shape_2d, strip_geometry)
from repro.kernels.stencil3d import (_aligns, _pad_to, ebisu3d,
                                     ebisu3d_padded,
                                     launch_geometry_3d, padded_shape_3d,
                                     xy_tile)
from repro.kernels.taps import ghost_extend, tap_sum

# plan-less fallback tiles (the request defaults the legacy entry points
# used; programs compiled without an explicit plan resolve one instead)
DEFAULT_BH_2D = 128
DEFAULT_ZC_3D = 16
DEFAULT_ZC_STREAM_2D = 64

_BUCKET = 64


# =========================================================== ProgramCache ==
class ProgramCache:
    """Bounded LRU cache with hit/miss/eviction counters — the explicit
    replacement for the module-global plan/launch dicts the executor used
    to hide state in.  Eviction only drops memoization: handles already
    returned stay valid.

    Thread-safe: the serving front door (``repro.serve``) dispatches from
    an event loop plus worker threads, so get/put/LRU bookkeeping run
    under a per-cache ``RLock``.  ``get_or_build`` holds the lock across
    the build — two threads racing on the same missing key build ONCE and
    observe the same value, instead of double-building and corrupting the
    LRU order.  (Builds here are plan derivations and ``jax.jit`` wrapper
    construction — cheap and non-reentrant on the same cache, so holding
    the lock is safe; tracing happens at first *call*, outside the lock.)

        c = ProgramCache(maxsize=2, name="demo")
        c.get_or_build("k", lambda: 42)    # -> 42 (miss, built)
        c.get("k"), c.stats()["hits"]      # -> 42, 1
    """

    def __init__(self, maxsize: int = 128, name: str = ""):
        if maxsize < 1:
            raise ValueError("maxsize must be >= 1")
        self.maxsize = maxsize
        self.name = name
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._lock = threading.RLock()
        self._d: OrderedDict = OrderedDict()

    def get(self, key, default=None):
        with self._lock:
            try:
                val = self._d[key]
            except KeyError:
                self.misses += 1
                return default
            self._d.move_to_end(key)
            self.hits += 1
            return val

    def put(self, key, value) -> None:
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)
                self.evictions += 1

    def get_or_build(self, key, build):
        """Return the cached value, building (and caching) it on miss —
        atomically: concurrent callers of the same missing key get the
        one built value."""
        sentinel = object()
        with self._lock:
            val = self.get(key, sentinel)
            if val is sentinel:
                val = build()
                self.put(key, val)
            return val

    def clear(self) -> None:
        """Drop all memoization (counted as evictions — the retry path in
        ``repro.serve`` reads the delta to classify eviction races)."""
        with self._lock:
            self.evictions += len(self._d)
            self._d.clear()

    def stats(self) -> dict:
        with self._lock:
            return {"name": self.name, "size": len(self._d),
                    "maxsize": self.maxsize, "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions}

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._d


PROGRAM_CACHE = ProgramCache(64, "programs")   # compile_stencil results
PLAN_CACHE = ProgramCache(256, "plans")        # §6 plans, shape-bucketed
RUNNER_CACHE = ProgramCache(128, "runners")    # jitted runners per launch


def cache_stats() -> dict:
    """Hit/miss/size counters for all three bounded caches.

        from repro.api import cache_stats, clear_caches
        cache_stats()["plans"]   # {'name': 'plans', 'size': ..., ...}
        clear_caches()           # drop memoization (handles stay valid)
    """
    return {c.name: c.stats()
            for c in (PROGRAM_CACHE, PLAN_CACHE, RUNNER_CACHE)}


def clear_caches() -> None:
    for c in (PROGRAM_CACHE, PLAN_CACHE, RUNNER_CACHE):
        c.clear()


def plan_bucketed(spec: StencilSpec, shape: tuple[int, ...],
                  hw: rl.HardwareModel = rl.TPU_V5E) -> EbisuPlan:
    """§6 plan memoized per (tap structure, 64-rounded domain, hardware)
    in the bounded ``PLAN_CACHE`` — a simulation loop over near-identical
    domains plans once per bucket.  Keyed on ``spec.signature`` (the tap
    set plus the cost-model numbers), NOT the registry name: user-defined
    specs plan without any registry lookup, and two differently-named
    specs with identical structure share one plan.

        p = plan_bucketed(get("j2d5pt"), (512, 512))
        p.t, p.block          # §6.2 depth, §6.4 tile
    """
    bucket = tuple(_pad_to(d, _BUCKET) for d in shape)
    key = (spec.signature, bucket, hw.name)
    return PLAN_CACHE.get_or_build(
        key, lambda: make_plan(spec, hw, domain=bucket))


# ======================================================= geometry / sweep ==
# The ONE place tile/grid/pad geometry is resolved (kernel rounding
# included) and the ONE place a sweep dispatches to a kernel.  ops.py and
# sweep.py delegate here.

def _tile_request(spec: StencilSpec, t: int, plan: EbisuPlan | None,
                  mode: str) -> dict:
    """The tile request a launch resolves from the plan (or the legacy
    request defaults), pre-kernel-rounding — the ONE derivation shared by
    geometry introspection and dispatch, so `prog.geometry()` can never
    drift from the tile `apply` actually launches."""
    halo = spec.halo(t)
    if spec.ndim == 2 and mode != "stream":
        bh = plan.block[0] if plan is not None else max(DEFAULT_BH_2D, halo)
        return dict(bh=max(bh, halo))
    if spec.ndim == 2:                   # stream mode: lifted 3-D launch
        zc = plan.block[0] if plan is not None else \
            max(DEFAULT_ZC_STREAM_2D, halo)
        return dict(zc=max(zc, halo),
                    tx=plan.block[1] if plan is not None else None)
    zc = plan.block[0] if plan is not None else max(DEFAULT_ZC_3D, halo)
    return dict(zc=max(zc, halo),
                ty=plan.block[1] if plan is not None else None,
                tx=plan.block[2] if plan is not None else None)


def resolve_geometry(spec: StencilSpec, t: int, shape: tuple[int, ...], *,
                     plan: EbisuPlan | None = None,
                     mode: str = "fused", aligned: bool = False) -> dict:
    """The geometry a one-sweep launch with these args will execute.

    Resolves the same tile/grid the kernels resolve (rounding included),
    so modeled traffic is derived from the launch that actually runs —
    not from the plan-less default tile (``fetched_cells``/``body_cells``
    are the input cells fetched and output cells per grid step).
    ``aligned`` is the Mosaic layout (rims rounded to the (8, 128) tile)
    that every launch lowered for the chip uses.

        g = resolve_geometry(get("j2d5pt"), 4, (512, 512))
        g["grid"], g["block"], g["halo"]    # what apply() will launch
    """
    req = _tile_request(spec, t, plan, mode)
    if spec.ndim == 2 and mode != "stream":
        bh, rim = strip_geometry(spec, t, req["bh"], aligned)
        hp, wp = padded_shape_2d(spec, t, bh, *shape, aligned)
        return dict(grid=(hp // bh,), block=(bh, shape[1]),
                    halo=spec.halo(t), padded=(hp, wp),
                    fetched_cells=(bh + 2 * rim) * wp,
                    body_cells=bh * wp)
    if spec.ndim == 2:                   # stream mode: lifted 3-D geometry
        return launch_geometry_3d(lift_2d_to_3d(spec), t,
                                  (shape[0], 1, shape[1]), aligned=aligned,
                                  **req)
    return launch_geometry_3d(spec, t, shape, aligned=aligned, **req)


def sweep_once(x: jnp.ndarray, spec: StencilSpec, t: int, *,
               plan: EbisuPlan | None = None, mode: str = "fused",
               interpret: bool = True,
               boundary: Boundary | None = None,
               compute_dtype=None) -> jnp.ndarray:
    """One temporally-blocked sweep — the sole plan→kernel dispatch path.

    When a §6 plan is supplied, its tile height/chunk depth
    (``plan.block``) and streaming batch (``plan.lazy_batch``) are wired
    into the kernels; its DMA pipeline depth
    (``plan.parallelism.num_buffers``) sizes the VMEM model the executor
    fits tiles with (DESIGN.md §17).  ``compute_dtype`` (default float32)
    is the dtype of the padded compute buffers the kernels run on.
    """
    lazy = plan.lazy_batch if plan is not None else None
    b = None if boundary is None or boundary.is_zero_dirichlet else boundary
    req = _tile_request(spec, t, plan, mode)
    if spec.ndim == 2:
        if mode == "stream":
            # the paper's 2-D scheme: stream y through the multi-queue
            # (no overlapped halo along the streamed dim); the planner's
            # §6.4 tile width (plan.block[1]) tiles x with overlapped halo.
            # The boundary is resolved before lifting (the size-1 lifted
            # axis must not be ghost-extended).
            if b is not None:
                from repro.kernels.taps import check_boundary, with_boundary
                check_boundary(spec.taps, b, t)
                return with_boundary(
                    x, 2, spec.halo(t), b,
                    lambda v: sweep_once(v, spec, t, plan=plan, mode=mode,
                                         interpret=interpret,
                                         compute_dtype=compute_dtype),
                    taps=spec.taps, t=t)
            y = ebisu3d(x[:, None, :], lift_2d_to_3d(spec), t,
                        lazy_batch=lazy,
                        interpret=interpret, compute_dtype=compute_dtype,
                        **req)
            return y[:, 0, :]
        return ebisu2d(x, spec, t, mode=mode,
                       interpret=interpret, boundary=b,
                       compute_dtype=compute_dtype, **req)
    return ebisu3d(x, spec, t, lazy_batch=lazy,
                   interpret=interpret, boundary=b,
                   compute_dtype=compute_dtype, **req)


# ===================================================== multi-sweep runner ==
def sweep_schedule(total_t: int, t: int) -> tuple[int, ...]:
    """Per-sweep depths covering ``total_t`` steps: full-depth sweeps plus
    one shallower remainder sweep when ``t`` does not divide ``total_t``.

        sweep_schedule(10, 4)    # -> (4, 4, 2)
        sweep_schedule(8, 4)     # -> (4, 4)
    """
    assert total_t >= 0 and t >= 1
    q, r = divmod(total_t, t)
    return (t,) * q + ((r,) if r else ())


def _grouped(schedule: tuple[int, ...]) -> list[tuple[int, int]]:
    """Runs of equal depth: [(depth, count), ...] — one layout per run."""
    out: list[list[int]] = []
    for d in schedule:
        if out and out[-1][0] == d:
            out[-1][1] += 1
        else:
            out.append([d, 1])
    return [(d, c) for d, c in out]


def _budget(hw: rl.HardwareModel) -> float:
    return hw.onchip_device_bytes or hw.onchip_bytes


def _sweep_tile_2d(spec: StencilSpec, t: int, shape: tuple[int, int],
                   hw: rl.HardwareModel, plan: EbisuPlan,
                   interpret: bool = False, aligned: bool = False) -> int:
    """Widest strip the §6 VMEM model affords (§6.4: wider before deeper),
    halving toward the plan's tile when the whole domain does not fit.

    ``interpret``: skip the widening entirely and keep the plan's own
    tile.  The §6.4 growth exists to fill real VMEM; the interpreter has
    none, and growing the strip past the plan's block is a measured
    superlinear pessimization on single-threaded CPU hosts (the
    pre-existing ``sweep/j2d5pt-T24`` bench regression — DESIGN.md §17).
    ``aligned``: the Mosaic strip layout (rims rounded to 8 rows).
    """
    height, width = shape
    halo = spec.halo(t)
    nbuf = plan.parallelism.num_buffers
    floor = max(min(plan.block[0], height), halo)
    if interpret:
        bh, _ = strip_geometry(spec, t, floor, aligned)
        return bh
    bh, _ = strip_geometry(spec, t, max(height, halo), aligned)
    while (vmem_required_2d(spec, t, bh, width, hw.s_cell, nbuf)
           > _budget(hw) and bh // 2 >= floor):
        bh, _ = strip_geometry(spec, t, bh // 2, aligned)
    return bh


def _sweep_tile_3d(spec: StencilSpec, t: int, shape: tuple[int, int, int],
                   hw: rl.HardwareModel, plan: EbisuPlan,
                   interpret: bool = False, aligned: bool = False
                   ) -> tuple[int, int | None, int | None, int]:
    """Deepest z chunk — and the streaming batch — the §6 VMEM model
    affords at the plan's xy tile.  The batch is fitted with the
    planner's own ``fit_streaming_batch``, so the executor never
    launches a configuration the shared model says does not fit: at the
    plan's own (zc, depth) the planner already proved one exists, and an
    off-plan depth too deep for the budget raises instead of silently
    over-committing on-chip memory.  ``interpret`` starts from the
    plan's own chunk instead of the whole domain (see
    :func:`_sweep_tile_2d` — the VMEM-filling growth is a pessimization
    where there is no VMEM)."""
    zdim, ydim, xdim = shape
    halo = spec.halo(t)
    nbuf = plan.parallelism.num_buffers
    ty, tx = plan.block[1], plan.block[2]
    ay, ax = _aligns(aligned)
    ty_r, tiled_y = xy_tile(spec, t, ydim, ty, ay)
    tx_r, tiled_x = xy_tile(spec, t, xdim, tx, ax)
    ny = ty_r + 2 * _pad_to(halo, ay) if tiled_y else ydim
    nx = tx_r + 2 * _pad_to(halo, ax) if tiled_x else xdim

    def fit_batch(zc_c: int) -> int | None:
        return fit_streaming_batch(spec, t, zc_c, ny, nx, hw.s_cell,
                                   nbuf, _budget(hw))

    zc = _pad_to(max(zdim, halo), halo)
    floor = min(zc, _pad_to(max(min(plan.block[0], zdim), halo), halo))
    if interpret:
        zc = floor
    batch = fit_batch(zc)
    while batch is None and zc > floor:
        zc = max(floor, _pad_to(zc // 2, halo))
        batch = fit_batch(zc)
    if batch is None:
        raise ValueError(
            f"{spec.name}: depth t={t} at xy tile ({ny}, {nx}) does not fit "
            f"the {hw.name} on-chip budget even at zc={zc} with a one-halo "
            f"batch — lower t toward the plan's depth ({plan.t})")
    return zc, (ty if tiled_y else None), (tx if tiled_x else None), batch


def _supports_donation() -> bool:
    return jax.default_backend() in ("tpu", "gpu")


def _build_chain(spec: StencilSpec, shape: tuple[int, ...], dtype,
                 total_t: int, depth: int, plan: EbisuPlan,
                 hw: rl.HardwareModel, mode: str, interpret: bool,
                 boundary: Boundary, compute_dtype=None,
                 batched: bool = False):
    """The multi-sweep schedule as an un-jitted f(x) -> x (DESIGN.md §9.3).

    Zero Dirichlet: the zero-copy padded chain — pad once per depth
    group, chain the padded kernel, crop once.  dirichlet(v), normalized
    taps: the same chain under the exact constant shift (still
    zero-copy).  dirichlet(v), tap sum s ≠ 1: the affine closure
    ``u' = Z_1(u − v) + v·s`` re-applied around every (depth-1) sweep —
    ``check_boundary`` guarantees no deeper sweep reaches this branch.
    periodic/reflect: the padded layout is NOT closed under the boundary,
    so each sweep re-pins the ghost halo from the evolved field and runs
    the zero-Dirichlet core on the extended domain (DESIGN.md §10).

    All compute buffers are ``compute_dtype`` (the program's policy —
    default float32); only the final result is cast to the program's
    storage ``dtype``.
    """
    groups = _grouped(sweep_schedule(total_t, depth))
    # interpret-mode strip floor (§17): the plan's own tile beats grown
    # strips on a single-threaded host — EXCEPT under vmap, where the
    # per-strip mask machinery is multiplied by the batch width and the
    # grown strip measures faster; batched chains keep the §6.4 growth
    tile_interp = interpret and not batched
    aligned = not interpret             # every Mosaic launch is aligned
    repin = boundary.kind in ("periodic", "reflect", "neumann")
    cdtype = jnp.dtype(compute_dtype) if compute_dtype else jnp.float32
    s = tap_sum(spec.taps)
    # per-sweep affine re-shift (s != 1): shift inside the sweep loop;
    # constant shift (s == 1): once around the whole chain (zero-copy)
    affine = (boundary.kind == "dirichlet" and boundary.value != 0.0
              and abs(s - 1.0) > 1e-6)
    shift = boundary.value if boundary.kind == "dirichlet" else 0.0

    def halo_of(d: int) -> int:
        return spec.halo(d) if repin else 0

    def pre(v, d):
        """Domain field -> sweep input, per sweep."""
        if affine:
            return v - jnp.asarray(shift, cdtype)
        return v

    def post(v, d):
        """Sweep output -> domain field, per sweep."""
        if affine:
            return v + jnp.asarray(shift * s ** d, cdtype)
        return v

    if spec.ndim == 2:
        height, width = shape

        def ext(d: int) -> tuple[int, int]:
            return height + 2 * halo_of(d), width + 2 * halo_of(d)

        cfg = {d: (_sweep_tile_2d(spec, d, ext(d), hw, plan, tile_interp,
                                  aligned),)
               for d, _ in groups}

        def chain(v: jnp.ndarray) -> jnp.ndarray:
            for d, count in groups:
                (bh,) = cfg[d]
                he, we = ext(d)
                halo = halo_of(d)
                hp, wp = padded_shape_2d(spec, d, bh, he, we, aligned)

                def sweep(xp, d=d, bh=bh, he=he, we=we):
                    return ebisu2d_padded(xp, spec, d, height=he, width=we,
                                          bh=bh, mode=mode,
                                          interpret=interpret)

                if repin or affine:
                    # layout not closed under the boundary: re-pin the
                    # ghost halo (periodic/reflect) or re-apply the
                    # affine shift (unnormalized Dirichlet) every sweep
                    for _ in range(count):
                        xp = jnp.zeros((hp, wp), cdtype).at[:he, :we].set(
                            ghost_extend(pre(v, d), 2, halo, boundary)
                            if repin else pre(v, d))
                        xp = sweep(xp)
                        v = post(xp[halo:halo + height,
                                    halo:halo + width], d)
                else:
                    # zero-copy: pad once, chain, crop once (§9.3)
                    xp = jnp.zeros((hp, wp), cdtype).at[
                        :height, :width].set(v)
                    for _ in range(count):
                        xp = sweep(xp)
                    v = xp[:height, :width]
            return v
    else:
        zdim, ydim, xdim = shape

        def ext3(d: int) -> tuple[int, int, int]:
            h = halo_of(d)
            return zdim + 2 * h, ydim + 2 * h, xdim + 2 * h

        cfg = {d: _sweep_tile_3d(spec, d, ext3(d), hw, plan, tile_interp,
                                 aligned)
               for d, _ in groups}

        def chain(v: jnp.ndarray) -> jnp.ndarray:
            for d, count in groups:
                zc, ty, tx, batch = cfg[d]
                ze, ye, xe = ext3(d)
                halo = halo_of(d)
                zp, yp, xp_ = padded_shape_3d(spec, d, (ze, ye, xe), zc=zc,
                                              ty=ty, tx=tx,
                                              aligned=aligned)

                def sweep(xp, d=d, zc=zc, ty=ty, tx=tx, batch=batch,
                          ze=ze, ye=ye, xe=xe):
                    return ebisu3d_padded(xp, spec, d, zdim=ze, ydim=ye,
                                          xdim=xe, zc=zc, ty=ty, tx=tx,
                                          lazy_batch=batch,
                                          interpret=interpret)

                if repin or affine:
                    for _ in range(count):
                        xp = jnp.zeros((zp, yp, xp_), cdtype).at[
                            :ze, :ye, :xe].set(
                                ghost_extend(pre(v, d), 3, halo, boundary)
                                if repin else pre(v, d))
                        xp = sweep(xp)
                        v = post(xp[halo:halo + zdim, halo:halo + ydim,
                                    halo:halo + xdim], d)
                else:
                    xp = jnp.zeros((zp, yp, xp_), cdtype).at[
                        :zdim, :ydim, :xdim].set(v)
                    for _ in range(count):
                        xp = sweep(xp)
                    v = xp[:zdim, :ydim, :xdim]
            return v

    if boundary.kind == "dirichlet" and boundary.value != 0.0 and not affine:
        def run(x):
            w = x.astype(cdtype) - shift
            return (chain(w) + shift).astype(dtype)
    else:
        def run(x):
            return chain(x.astype(cdtype)).astype(dtype)

    return run


# ------------------------------------------- 2-D donated padded carry ------
def _padded_chain_2d(xp, spec, total_t, *, t, height, width, bh, mode,
                     interpret):
    assert total_t % t == 0, "padded chaining needs a uniform sweep depth"
    for _ in range(total_t // t):
        xp = ebisu2d_padded(xp, spec, t, height=height, width=width, bh=bh,
                            mode=mode, interpret=interpret)
    return xp


@functools.lru_cache(maxsize=None)
def _padded_runner_2d(donate: bool):
    return jax.jit(_padded_chain_2d,
                   static_argnames=("spec", "total_t", "t", "height",
                                    "width", "bh", "mode", "interpret"),
                   donate_argnums=(0,) if donate else ())


def run_sweeps_padded(xp: jnp.ndarray, spec: StencilSpec, total_t: int, *,
                      t: int, height: int, width: int, bh: int,
                      mode: str = "fused",
                      interpret: bool = True) -> jnp.ndarray:
    """Padded-layout sweep chain (2-D, zero Dirichlet), ``t | total_t``.

    The caller owns the padded buffer and the layout never changes, so
    the carry is donated where the backend supports it — XLA ping-pongs
    two buffers across sweeps instead of allocating per sweep
    (DESIGN.md §9.3).  The donation choice is made at call time so
    importing this module never initializes a JAX backend."""
    return _padded_runner_2d(_supports_donation())(
        xp, spec, total_t, t=t, height=height, width=width, bh=bh,
        mode=mode, interpret=interpret)


# ============================================================== programs ==
def _plan_key(plan: EbisuPlan | None):
    if plan is None:
        return None
    return (plan.hw_name, plan.t, plan.block, plan.lazy_batch,
            plan.parallelism.num_buffers)


class StencilProgram:
    """An immutable compiled stencil: spec + domain shape + §6 plan +
    boundary + launch mode (+ optional device mesh), with memoized
    runners.  Construct via :func:`compile_stencil`:

        prog = compile_stencil(get("j2d5pt"), (512, 512), t=4)
        y  = prog.apply(x)            # one temporally-blocked sweep
        y  = prog.run(x, 64)          # 64 steps under one jit
        ys = prog.run_batched(xs, 64) # leading batch axis, one dispatch
    """

    def __init__(self, key, spec: StencilSpec, shape: tuple[int, ...],
                 dtype, t: int, plan: EbisuPlan | None,
                 hw: rl.HardwareModel, boundary: Boundary, mode: str,
                 interpret: bool, compute_dtype=None, mesh=None,
                 tuned: dict | None = None):
        self._key = key
        self.spec = spec
        self.shape = shape
        self.dtype = dtype
        self.t = t
        self.plan = plan
        self.hw = hw
        self.boundary = boundary
        self.mode = mode
        self.interpret = interpret
        self.mesh = mesh
        self.compute_dtype = (jnp.dtype(compute_dtype) if compute_dtype
                              else jnp.float32)
        # provenance of a mode="tuned" resolution: {"source": "plandb",
        # "record": ...} on a DB hit, {"source": "analytic_fallback"} on
        # a miss, None for programs compiled with an explicit mode
        self.tuned = tuned

    # ------------------------------------------------------- execution ----
    def _check(self, x, batched: bool = False):
        want = ((-1,) + self.shape) if batched else self.shape
        if x.ndim != len(want) or any(
                w != -1 and n != w for n, w in zip(x.shape, want)):
            raise ValueError(
                f"program compiled for shape {self.shape} "
                f"({'batched ' if batched else ''}got {x.shape}); "
                "compile_stencil a new program for a new domain shape")

    def apply(self, x: jnp.ndarray, t: int | None = None) -> jnp.ndarray:
        """One temporally-blocked sweep of depth ``t`` (default: the
        program's compiled depth).

            y = prog.apply(x)        # == t plain steps, one memory pass
            y = prog.apply(x, t=2)   # off-plan depth, separately cached
        """
        self._check(x)
        depth = self.t if t is None else t
        if depth < 1:
            raise ValueError(f"temporal depth must be >= 1, got {depth} "
                             "(run(x, 0) is the identity)")
        fn = RUNNER_CACHE.get_or_build(
            (self._key, "apply", depth),
            lambda: jax.jit(functools.partial(
                sweep_once, spec=self.spec, t=depth, plan=self.plan,
                mode=self.mode, interpret=self.interpret,
                boundary=self.boundary,
                compute_dtype=self.compute_dtype)))
        return fn(x)

    def _run_fn(self, total_t: int, batched: bool = False):
        plan = self.plan or plan_bucketed(self.spec, self.shape, self.hw)
        depth = max(1, min(self.t, total_t))
        if self.spec.ndim == 2 and self.mode not in ("fused", "scratch"):
            raise ValueError(
                f"run supports 2-D modes 'fused'/'scratch', got "
                f"{self.mode!r} (use apply for the lifted 'stream' path)")
        return _build_chain(self.spec, self.shape, self.dtype, total_t,
                            depth, plan, self.hw, self.mode,
                            self.interpret, self.boundary,
                            compute_dtype=self.compute_dtype,
                            batched=batched)

    def run(self, x: jnp.ndarray, total_t: int) -> jnp.ndarray:
        """``total_t`` steps as chained temporally-blocked sweeps under a
        single cached jit — the zero-copy executor (remainder sweep
        included when the program depth does not divide ``total_t``).

            prog = compile_stencil(spec, x.shape, t=4)
            y = prog.run(x, 64)     # 16 sweeps: pad once, chain, crop
            y = prog.run(x, 10)     # sweeps of depth 4, 4, then 2
        """
        self._check(x)
        if total_t == 0:
            return x
        fn = RUNNER_CACHE.get_or_build(
            (self._key, "run", total_t),
            lambda: jax.jit(self._run_fn(total_t)))
        return fn(x)

    def run_batched(self, xs: jnp.ndarray,
                    total_t: int | None = None) -> jnp.ndarray:
        """A leading batch axis of independent fields through ONE vmapped
        padded runner — a single jitted dispatch for the whole batch,
        instead of a Python loop of per-field launches.

            xs = jnp.stack([x0, x1, x2])        # (3, *prog.shape)
            ys = prog.run_batched(xs, 64)       # one dispatch, 3 fields
        """
        self._check(xs, batched=True)
        total_t = self.t if total_t is None else total_t
        if total_t == 0:
            return xs
        fn = RUNNER_CACHE.get_or_build(
            (self._key, "batched", total_t),
            lambda: jax.jit(jax.vmap(self._run_fn(total_t, batched=True))))
        return fn(xs)

    def run_sharded(self, x: jnp.ndarray, total_t: int) -> jnp.ndarray:
        """``total_t`` steps over the program's device mesh, exchanging
        deep ghost zones **once per temporal block** instead of once per
        step (DESIGN.md §12; guide: ``docs/sharding.md``).

        Each device holds one uniform shard (mesh axis ``k`` over tensor
        dim ``k``); per block of depth ``d``, neighbor shards swap
        ``d·radius``-deep halo slabs (one ``ppermute`` round per sharded
        dim, corners via two hops) and run the trapezoid-narrowed chain
        locally.  The whole schedule — remainder block included — is one
        cached jit; the operand buffer is donated to it on backends that
        support donation (pass ``x.copy()`` to keep ``x`` alive there).
        A mesh of total size 1 falls back transparently to :meth:`run`.

            prog = compile_stencil(spec, (256, 512), t=4, mesh=(2, 4))
            y = prog.run_sharded(x, 64)       # 16 exchange rounds, not 64

        Requires a program compiled with ``mesh=``; the output is a
        global ``jax.Array`` sharded like the input placement.
        """
        self._check(x)
        if self.mesh is None:
            raise ValueError(
                "run_sharded needs a mesh-compiled program: "
                "compile_stencil(spec, shape, mesh=(2, 4)) or mesh=8 — "
                "see docs/sharding.md")
        if total_t == 0:
            return x
        if self.mesh.size == 1:                 # 1-device mesh: no seams
            return self.run(x, total_t)
        from repro.api import sharded
        fn = RUNNER_CACHE.get_or_build(
            (self._key, "sharded", total_t),
            lambda: jax.jit(
                sharded.build_sharded_runner(self, total_t),
                donate_argnums=(0,) if _supports_donation() else ()))
        xs = jax.device_put(x, sharded.operand_sharding(self))
        return fn(xs)

    def run_padded(self, xp: jnp.ndarray, total_t: int) -> jnp.ndarray:
        """Uniform-depth padded-layout chain with a donated carry (2-D,
        zero Dirichlet, ``t | total_t``); see :func:`run_sweeps_padded`.
        The caller owns the ``padded_shape`` buffer across calls."""
        if (self.spec.ndim != 2 or not self.boundary.is_zero_dirichlet
                or self.mode not in ("fused", "scratch")):
            raise ValueError("run_padded is the 2-D zero-Dirichlet "
                             "padded-carry path (fused/scratch); use run()")
        if xp.dtype != self.compute_dtype:
            raise ValueError(
                f"run_padded carry is the compute buffer: expected dtype "
                f"{self.compute_dtype.name}, got {xp.dtype.name} "
                "(the caller owns the padded buffer at the program's "
                "compute_dtype)")
        bh = self.geometry()["block"][0]
        return run_sweeps_padded(
            xp, self.spec, total_t, t=self.t, height=self.shape[0],
            width=self.shape[1], bh=bh, mode=self.mode,
            interpret=self.interpret)

    # ----------------------------------------------- resumable campaigns ----
    def run_resumable(self, x, total_t: int, *, store, every: int = 1,
                      **kwargs):
        """``total_t`` steps as checkpointed legs of ``every`` temporal
        blocks, resumable after a crash and **bit-exact** equal to
        :meth:`run` (guide: ``docs/resilience.md``).

            store = CampaignStore("/ckpt/heat2d")
            y = prog.run_resumable(x, 512, store=store, every=2)
            # ... SIGKILL mid-campaign ...
            y = prog.run_resumable(x, 512, store=store)   # picks up

        Keyword knobs (``policy=``, ``health=``, ``faults=``, ``clock=``,
        ``resume=``, ``on_leg=``) pass through to
        :func:`repro.resilient.runner.run_campaign`; returns its
        :class:`~repro.resilient.runner.CampaignReport` (the final field
        is ``report.result``).
        """
        from repro.resilient import runner
        return runner.run_campaign(self, x, total_t, store=store,
                                   every=every, sharded=False, **kwargs)

    def run_sharded_resumable(self, x, total_t: int, *, store,
                              every: int = 1, **kwargs):
        """The sharded twin of :meth:`run_resumable`: checkpointed legs
        of :meth:`run_sharded` over the program's mesh, plus elastic
        restore onto a smaller mesh when a device drops (the default
        ``RetryPolicy(elastic=True)``)."""
        if self.mesh is None:
            raise ValueError(
                "run_sharded_resumable needs a mesh-compiled program: "
                "compile_stencil(spec, shape, mesh=(2, 4)) — "
                "see docs/sharding.md")
        from repro.resilient import runner
        return runner.run_campaign(self, x, total_t, store=store,
                                   every=every, sharded=True, **kwargs)

    # ---------------------------------------------------- introspection ----
    def fingerprint(self) -> dict:
        """A JSON-safe identity card for checkpoint manifests: what a
        resumed campaign must match bit-for-bit (spec signature, shape,
        dtypes, boundary, depth, mode, hw) plus what may drift only
        elastically (mesh, plan) — see ``repro.resilient.store``."""
        return {
            "spec_name": self.spec.name,
            "spec_signature": repr(self.spec.signature),
            "shape": list(self.shape),
            "dtype": self.dtype.name,
            "compute_dtype": self.compute_dtype.name,
            "boundary": repr(self.boundary),
            "t": int(self.t),
            "mode": self.mode,
            "hw": self.hw.name,
            "plan": repr(_plan_key(self.plan)),
            "mesh": (None if self.mesh is None
                     else {k: int(v) for k, v in self.mesh.shape.items()}),
        }

    def compute_shape(self, t: int | None = None) -> tuple[int, ...]:
        """The domain the kernels actually compute: the program shape,
        ghost-extended by ``t·rad`` per side for re-pinning boundaries."""
        depth = self.t if t is None else t
        if self.boundary.kind in ("periodic", "reflect", "neumann"):
            h = self.spec.halo(depth)
            return tuple(n + 2 * h for n in self.shape)
        return self.shape

    def geometry(self, t: int | None = None) -> dict:
        """The launch geometry a depth-``t`` sweep resolves (tile, grid,
        halo, padded layout, halo-exact fetched/body cells)."""
        depth = self.t if t is None else t
        return resolve_geometry(self.spec, depth, self.compute_shape(depth),
                                plan=self.plan, mode=self.mode,
                                aligned=not self.interpret)

    def cost(self, t: int | None = None) -> rl.RooflineResult:
        """§5 practical-attainable estimate at depth ``t``.  At the plan's
        own depth this is the plan's prediction (redundancy/sync valid
        fractions included); off-plan depths get the ideal-V roofline."""
        depth = self.t if t is None else t
        if self.plan is not None and depth == self.plan.t:
            return self.plan.pp
        return rl.attainable(self.spec, depth, self.hw, rst=True,
                             d_all=math.prod(self.shape))

    def cache_stats(self) -> dict:
        """Counters of the module's bounded caches (programs, plans,
        runners) — see :func:`cache_stats`."""
        return cache_stats()

    def __repr__(self) -> str:
        mesh = (f", mesh={dict(self.mesh.shape)}" if self.mesh is not None
                else "")
        return (f"StencilProgram({self.spec.name}, shape={self.shape}, "
                f"t={self.t}, boundary={self.boundary!r}, "
                f"mode={self.mode!r}, hw={self.hw.name}, "
                f"dtype={self.dtype.name}/{self.compute_dtype.name}, "
                f"interpret={self.interpret}{mesh})")


def resolve_compute_dtype(dtype, compute_dtype=None):
    """The program dtype policy: compute in ``compute_dtype`` when given,
    else in the storage dtype promoted to at least float32 (bf16/f16
    fields are stored narrow but stepped in f32 — one rounding at the
    end instead of one per sweep; f64 storage computes in f64).

        resolve_compute_dtype(jnp.bfloat16)              # -> float32
        resolve_compute_dtype(jnp.float32, jnp.float64)  # -> float64
    """
    if compute_dtype is not None:
        cd = jnp.dtype(compute_dtype)
        if not jnp.issubdtype(cd, jnp.floating):
            raise ValueError(
                f"compute_dtype must be a floating dtype, got {cd.name}")
        return cd
    d = jnp.dtype(dtype)
    if not jnp.issubdtype(d, jnp.floating):
        raise ValueError(
            f"stencil cell dtype must be floating, got {d.name} "
            "(pass dtype=jnp.float32/bfloat16/... to compile_stencil)")
    return jnp.promote_types(d, jnp.float32)


def compile_stencil(spec: StencilSpec, shape: tuple[int, ...], *,
                    dtype=jnp.float32, t: int | None = None,
                    hw: rl.HardwareModel = rl.TPU_V5E,
                    boundary: Boundary | None = None, mode: str = "fused",
                    interpret: bool | None = None,
                    plan: EbisuPlan | None | str = "auto",
                    compute_dtype=None, mesh=None,
                    plan_db=None) -> StencilProgram:
    """Compile a stencil to an immutable :class:`StencilProgram`.

        from repro.api import Boundary, compile_stencil
        from repro.core.stencil_spec import get
        prog = compile_stencil(get("j3d7pt"), (256, 288, 384), t=4,
                               boundary=Boundary.periodic())
        y = prog.run(x, 64)

    Accepts ANY validated :class:`StencilSpec` — the Table-2 registry and
    ``repro.api.define_stencil`` products are equals here: the plan is
    derived from the tap structure (``plan_bucketed`` keys on
    ``spec.signature``), never from a registry lookup.

    Resolves — exactly once — the §6 plan (shape-bucketed, memoized),
    the boundary execution strategy (validated against the tap set *and*
    the chain depth: the affine Dirichlet closure, DESIGN.md §11.3), the
    dtype policy (``dtype`` is cell storage; ``compute_dtype`` — default
    storage promoted to ≥ f32 — is what the kernels and the multi-sweep
    chain run in), and the interpret/lowering choice (Pallas-TPU on TPU
    backends, interpreter elsewhere).  Programs are memoized in the
    bounded ``PROGRAM_CACHE``; recompiling with identical arguments
    returns the same handle.

    ``t`` is the per-sweep temporal depth (default: the plan's §6.2
    choice).  ``plan`` is normally derived ("auto"); pass an explicit
    ``EbisuPlan`` to pin tiles (autotuning), or ``None`` for the legacy
    request-default tiles the deprecated entry points used.

    ``mode="tuned"`` resolves (t, block, lazy_batch, kernel family) from
    the persistent plan DB (``repro.tuning``, guide in
    ``docs/tuning.md``): a DB hit replays the *measured* winner with
    zero search or timing; a miss falls back to the analytic plan
    (``mode="fused"``) — run ``repro.tuning.tune(...)`` or ``python -m
    repro.tuning sweep`` to warm the DB.  Either way ``prog.tuned``
    records the provenance.  ``plan_db`` is a ``PlanDB``, a directory
    path, or ``None`` for the default location; it is only consulted
    for ``mode="tuned"``.

    ``mesh`` (a ``jax.sharding.Mesh``, an int, or a tuple — mesh axis
    ``k`` shards tensor dim ``k``) makes the program multi-device: the §6
    plan is resolved **per shard** (domain/mesh, since each device sees
    one shard plus its ``t·radius`` block halo), shard uniformity and
    halo-fit are validated here with the fix spelled out, and
    :meth:`StencilProgram.run_sharded` becomes available
    (DESIGN.md §12, guide in ``docs/sharding.md``)::

        prog = compile_stencil(spec, (256, 512), t=4, mesh=(2, 4))
        y = prog.run_sharded(x, 64)     # one halo exchange per 4 steps
    """
    validate_spec(spec)
    shape = tuple(int(n) for n in shape)
    if len(shape) != spec.ndim:
        raise ValueError(f"{spec.name} is {spec.ndim}-D; got shape {shape}")
    tuned_info = None
    if mode == "tuned":
        # plan resolution only: the DB record supplies depth, block,
        # batch AND the kernel family — explicit overrides would make
        # the record a lie, so they are refused with the fix spelled out
        if t is not None:
            raise ValueError(
                "mode='tuned' resolves t from the plan DB; drop t= "
                "(or compile mode='fused' with an explicit t to pin "
                "depth yourself)")
        if not (isinstance(plan, str) and plan == "auto"):
            raise ValueError(
                "mode='tuned' resolves the plan from the plan DB; drop "
                "plan= (pass an explicit EbisuPlan with mode='fused'/"
                "'scratch' to pin tiles yourself)")
        if mesh is not None:
            raise ValueError(
                "mode='tuned' records are single-device measurements; "
                "compile mesh= programs with an explicit mode (the "
                "per-shard plan is derived analytically)")
        from repro.tuning import plandb as _plandb
        itp = (interpret if interpret is not None
               else jax.default_backend() != "tpu")
        rec = _plandb.resolve_db(plan_db).lookup(
            spec, shape, "interpret" if itp else "native")
        if rec is not None:
            plan = _plandb.plan_from_record(spec, shape, hw, rec)
            t = plan.t
            mode = rec["plan"]["exec_mode"]
            tuned_info = {"source": "plandb", "record": rec}
        else:
            mode = "fused"
            tuned_info = {"source": "analytic_fallback"}
    valid_modes = ("fused", "scratch", "stream") if spec.ndim == 2 \
        else ("fused", "scratch")        # 3-D ignores scratch (seed compat)
    if mode not in valid_modes:
        raise ValueError(f"unknown mode {mode!r} for a {spec.ndim}-D spec; "
                         f"expected one of {valid_modes}")
    boundary = ZERO if boundary is None else boundary
    cdtype = resolve_compute_dtype(dtype, compute_dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from repro.api import sharded as _sharded
    mesh = _sharded.resolve_mesh(mesh, spec.ndim)
    plan_shape = shape
    if mesh is not None:
        # shard uniformity first (depth-1 halo fit is a subset of the
        # full-depth check below), then the per-shard planning pass:
        # each device is one big tile — plan for the shard it owns, not
        # the global domain (DESIGN.md §12)
        _sharded.validate_mesh_for(spec, shape, mesh, 1, boundary)
        plan_shape = _sharded.shard_extents(shape, mesh)
    if isinstance(plan, str):
        if plan != "auto":
            raise ValueError(f"plan must be an EbisuPlan, None, or 'auto'; "
                             f"got {plan!r}")
        plan = plan_bucketed(spec, plan_shape, hw)
    depth = t if t is not None else (plan.t if plan is not None else 1)
    if depth < 1:
        raise ValueError(f"temporal depth must be >= 1, got {depth}")
    boundary.validate_for(spec, t=depth)
    if mesh is not None:
        _sharded.validate_mesh_for(spec, shape, mesh, depth, boundary)
    key = (spec, shape, jnp.dtype(dtype).name, depth, hw.name,
           boundary, mode, bool(interpret), _plan_key(plan), cdtype.name,
           _sharded.mesh_key(mesh),
           None if tuned_info is None else ("tuned", tuned_info["source"]))
    cached = PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached
    prog = StencilProgram(key, spec, shape, jnp.dtype(dtype), depth, plan,
                          hw, boundary, mode, bool(interpret),
                          compute_dtype=cdtype, mesh=mesh,
                          tuned=tuned_info)
    PROGRAM_CACHE.put(key, prog)
    return prog


def deprecated_entry(name: str, replacement: str) -> None:
    """One-per-call-site deprecation notice for the legacy entry points
    (policy in README.md: shims stay for two PR cycles, geometry/dispatch
    already lives here).

    Emitted strictly at *call* time, never at import time — importing
    ``repro.kernels.ops`` / ``repro.kernels.sweep`` stays silent, so
    modules that merely transit the legacy names (test collection,
    introspection) produce no warnings; ``benchmarks/`` drives
    ``repro.api`` directly and emits none at all.
    """
    warnings.warn(f"{name} is deprecated; use {replacement} "
                  "(repro.api) instead", DeprecationWarning, stacklevel=3)
