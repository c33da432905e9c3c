"""Stencil driver: the paper's suite AND user-defined stencils, end-to-end.

Quick start (the three-line compile→run flow):

    from repro.api import Boundary, compile_stencil, define_stencil
    spec = define_stencil([((0, 0), 0.6), ((0, 1), 0.1), ...])  # any taps
    prog = compile_stencil(spec, x.shape, t=4, boundary=Boundary.periodic())
    y = prog.run(x, T=64)         # 64 steps as chained zero-copy sweeps

Custom stencils are drivable straight from the CLI — the derived §5 cost
model is printed so the analytic machinery is inspectable:

    python -m repro.launch.stencil_run \
        --taps '[[[0,0],0.6],[[0,1],0.1],[[0,-1],0.1],[[1,0],0.1],[[-1,0],0.1]]' --t 2
    python -m repro.launch.stencil_run --spec-json my_stencil.json

``--mesh ZxY`` compiles the program onto a device mesh and runs it through
``run_sharded`` — deep ghost zones exchanged once per temporal block
(``docs/sharding.md``); on a CPU-only host the device count is faked
automatically.  ``--distributed`` is the older jnp reference scheme over
the host mesh; otherwise the compiled program drives the Pallas kernels
(lowered by Mosaic on a TPU, interpret mode on CPU)."""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.api import (Boundary, compile_stencil, define_stencil,
                       parse_taps, spec_from_json)
from repro.core import roofline as rl
from repro.core.stencil_spec import StencilSpec, TABLE2, get
from repro.kernels import ref
from repro.launch.compile_cache import use_compile_cache
from repro.stencils.data import init_domain, reduced_domain


def parse_mesh(text: str) -> tuple[int, ...]:
    """'8' | '2x4' | '2,4' → mesh shape tuple (axis k shards tensor dim k)."""
    try:
        shape = tuple(int(p) for p in text.replace(",", "x").split("x"))
        if not shape or any(n < 1 for n in shape):
            raise ValueError
        return shape
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"bad mesh {text!r}; use an int ('8') or a shape ('2x4')")


def parse_boundary(text: str) -> Boundary:
    """'dirichlet[:v]' | 'periodic' | 'reflect' | 'neumann[:flux]'
    → Boundary."""
    kind, _, val = text.partition(":")
    if kind == "dirichlet":
        return Boundary.dirichlet(float(val) if val else 0.0)
    if kind == "periodic":
        return Boundary.periodic()
    if kind == "reflect":
        return Boundary.reflect()
    if kind == "neumann":
        return Boundary.neumann(float(val) if val else 0.0)
    raise argparse.ArgumentTypeError(
        f"unknown boundary {text!r}; use dirichlet[:v] | periodic | "
        f"reflect | neumann[:flux]")


def cost_summary_line(spec: StencilSpec,
                      hw: rl.HardwareModel = rl.TPU_V5E) -> str:
    """One line of the derived §5 cost model (flagging any overrides)."""
    c = rl.spec_cost_summary(spec, hw)
    over = f" overrides={','.join(c['overridden'])}" if c["overridden"] else ""
    return (f"[spec]    {spec.name:11s} {c['ndim']}D r={c['radius']} "
            f"{c['npoints']}pt {c['shape_kind']} tap_sum={c['tap_sum']:.4g} | "
            f"flops/cell={c['flops_per_cell']:g} "
            f"a_sm={c['a_sm']:g} a_sm_rst={c['a_sm_rst']:g}{over} | "
            f"eq17 t*={c['desired_depth_eq17']:.1f} "
            f"eq23 w_min={c['min_tile_width_eq23']:.0f}")


def run_single(spec: StencilSpec | str, *, t: int | None = None,
               scale: int = 64, boundary: Boundary | None = None,
               check: bool = True, summary: bool = False):
    spec = get(spec) if isinstance(spec, str) else spec
    shape = reduced_domain(spec, scale)
    boundary = boundary or Boundary.dirichlet(0.0)
    # unnormalized Dirichlet admits only depth-1 sweeps (affine closure)
    depth_cap = 1 if (boundary.kind == "dirichlet" and boundary.value != 0.0
                      and abs(spec.tap_sum - 1.0) > 1e-6) else None
    prog = compile_stencil(spec, shape, boundary=boundary, t=depth_cap)
    depth = t or min(prog.t, 6)
    x = init_domain(spec, shape)
    t0 = time.time()
    if depth > prog.t:
        # deeper than the plan's sweet spot: run T = depth total steps as
        # plan-depth sweeps through the program's zero-copy executor
        # instead of one over-deep sweep (whose halo would eat the tile)
        y = prog.run(x, depth)
        how = f"run(T={depth}, t={prog.t})"
    else:
        y = prog.apply(x, t=depth)
        how = "single-sweep"
    y.block_until_ready()
    dt = time.time() - t0
    plan = prog.plan
    if summary:
        print(cost_summary_line(spec, prog.hw), flush=True)
    line = (f"[stencil] {spec.name:11s} domain={shape} t={depth} {how} "
            f"boundary={boundary!r} "
            f"plan(t={plan.t}, tile={plan.block}, "
            f"lazy_batch={plan.lazy_batch}, "
            f"buffers={plan.parallelism.num_buffers}) "
            f"{dt*1e3:.0f}ms")
    if check:
        want = ref.reference(x, spec, depth, boundary=boundary)
        err = float(jnp.abs(y - want).max())
        line += f" maxerr={err:.2e}"
        assert err < 1e-4
    print(line, flush=True)
    return y


def run_sharded(spec: StencilSpec | str, mesh_shape: tuple[int, ...], *,
                t: int | None = None, scale: int = 64,
                boundary: Boundary | None = None, total_t: int | None = None,
                check: bool = True):
    """Drive ``compile_stencil(..., mesh=)`` + ``run_sharded`` end-to-end:
    shard the domain over the mesh, run ``T`` steps with one deep-halo
    exchange per temporal block, and (optionally) check against the
    per-step oracle.  Domain dims are rounded up to shard uniformly."""
    from repro.api import planned_exchange_rounds

    spec = get(spec) if isinstance(spec, str) else spec
    boundary = boundary or Boundary.dirichlet(0.0)
    shape = list(reduced_domain(spec, scale))
    for d, n in enumerate(mesh_shape):
        # uniform shards, each wide enough for the deep block halo
        min_shard = (t or 2) * spec.radius + 1
        shape[d] = n * max(-(-shape[d] // n), min_shard)
    shape = tuple(shape)
    if t is None:
        # default depth: run_single's cap, further bounded so the block
        # halo t*radius fits inside one shard (one neighbor hop)
        caps = [shape[d] // n // spec.radius
                for d, n in enumerate(mesh_shape) if n > 1]
        cap = min(caps) - (boundary.kind == "reflect") if caps else 6
        t = max(1, min(6, cap))
    prog = compile_stencil(spec, shape, t=t, boundary=boundary,
                           mesh=mesh_shape)
    total = total_t if total_t is not None else 2 * prog.t + 1
    x = init_domain(spec, shape)
    t0 = time.time()
    y = prog.run_sharded(x, total)
    y.block_until_ready()
    dt = time.time() - t0
    rounds = planned_exchange_rounds(total, prog.t)
    line = (f"[sharded] {spec.name:11s} domain={shape} "
            f"mesh={'x'.join(map(str, mesh_shape))} T={total} t={prog.t} "
            f"exchanges={rounds} (vs {total} per-step) {dt*1e3:.0f}ms")
    if check:
        want = ref.reference(x, spec, total, boundary=boundary)
        err = float(jnp.abs(y - want).max())
        line += f" maxerr={err:.2e}"
        assert err < 1e-4
    print(line, flush=True)
    return y


def run_campaign_cli(spec: StencilSpec | str, *, checkpoint_dir: str,
                     mesh_shape: tuple[int, ...] | None = None,
                     t: int | None = None, scale: int = 64,
                     boundary: Boundary | None = None,
                     total_t: int | None = None, every: int = 1,
                     resume: str = "auto", kill_after_leg: int | None = None,
                     out: str | None = None):
    """Drive a checkpointed campaign (``docs/resilience.md``): ``T`` steps
    as legs of ``every`` temporal blocks, checkpointing into
    ``checkpoint_dir``, resumable after a crash and bit-exact equal to
    the uninterrupted run.  ``kill_after_leg`` SIGKILLs the process after
    that leg's checkpoint lands — the CI crash-restart smoke:

        python -m repro.launch.stencil_run --stencil j2d5pt \\
            --checkpoint-dir /tmp/ck --T 24 --kill-after-leg 2   # dies (137)
        python -m repro.launch.stencil_run --stencil j2d5pt \\
            --checkpoint-dir /tmp/ck --T 24 --resume auto --out y.npy
    """
    import numpy as np

    from repro.resilient import CampaignStore

    spec = get(spec) if isinstance(spec, str) else spec
    boundary = boundary or Boundary.dirichlet(0.0)
    if mesh_shape:
        shape = list(reduced_domain(spec, scale))
        for d, n in enumerate(mesh_shape):
            min_shard = (t or 2) * spec.radius + 1
            shape[d] = n * max(-(-shape[d] // n), min_shard)
        shape = tuple(shape)
        prog = compile_stencil(spec, shape, t=t or 2, boundary=boundary,
                               mesh=mesh_shape)
    else:
        shape = reduced_domain(spec, scale)
        prog = compile_stencil(spec, shape, t=t, boundary=boundary)
    total = total_t if total_t is not None else 2 * prog.t + 1
    x = init_domain(spec, shape)
    store = CampaignStore(checkpoint_dir)
    on_leg = None
    if kill_after_leg is not None:
        import os
        import signal

        def on_leg(leg, steps_done):
            if leg >= kill_after_leg:
                store.wait()     # the landed checkpoint survives the kill
                print(f"[campaign] injected crash after leg {leg} "
                      f"({steps_done}/{total} steps)", flush=True)
                os.kill(os.getpid(), signal.SIGKILL)

    t0 = time.time()
    runner = (prog.run_sharded_resumable if mesh_shape
              else prog.run_resumable)
    rep = runner(x, total, store=store, every=every, resume=resume,
                 on_leg=on_leg)
    rep.result.block_until_ready()
    dt = time.time() - t0
    resumed = (f" resumed@leg{rep.resumed_from}"
               if rep.resumed_from is not None else "")
    print(f"[campaign] {spec.name:11s} domain={shape} T={total} "
          f"t={prog.t} legs={rep.legs_total} every={every}"
          f"{resumed} ckpts={rep.checkpoints_written} "
          f"rms={rep.final_rms:.4g} {dt*1e3:.0f}ms", flush=True)
    if out:
        np.save(out, np.asarray(rep.result))
        print(f"[campaign] final field -> {out}", flush=True)
    return rep


def run_system_cli(name: str, *, t: int | None = None, scale: int = 64,
                   boundary: Boundary | None = None,
                   total_t: int | None = None, check: bool = True):
    """Drive a coupled system end-to-end (``docs/systems.md``): compile
    the library system, run ``T`` steps as fused multi-field sweeps, and
    (optionally) check the result is finite and matches the unfused
    per-field-per-step lockstep reference.

        python -m repro.launch.stencil_run --system gray-scott --t 4
    """
    import numpy as np

    from repro.systems import compile_system, get_system

    spec = get_system(name)
    boundary = boundary or Boundary.periodic()
    shape = (scale, scale)[:spec.ndim] if spec.ndim == 2 else \
        (scale, scale, scale)
    prog = compile_system(spec, shape, t=t or 4, boundary=boundary)
    total = total_t if total_t is not None else 2 * prog.t + 1
    rng = np.random.default_rng(0)
    fields = {f: jnp.asarray(rng.uniform(0.2, 0.8, shape).astype(np.float32))
              for f in spec.fields}
    t0 = time.time()
    out = prog.run(fields, total)
    jax.block_until_ready(out)
    dt = time.time() - t0
    line = (f"[system]  {spec.name:20s} fields={len(spec.fields)} "
            f"domain={shape} T={total} t={prog.t} "
            f"boundary={boundary!r} {dt*1e3:.0f}ms")
    if check:
        assert all(bool(jnp.isfinite(v).all()) for v in out.values()), \
            f"{spec.name}: non-finite output"
        want = prog.run_lockstep(fields, total)
        err = max(float(jnp.abs(out[f] - want[f]).max())
                  for f in spec.fields)
        line += f" maxerr_vs_lockstep={err:.2e}"
        assert err < 2e-5
    print(line, flush=True)
    return out


def run_distributed(name: str, *, t_total: int = 4, t_block: int = 2,
                    scale: int = 64):
    from repro.core.distributed import make_distributed_stencil
    from repro.launch.mesh import make_mesh

    spec = get(name)
    n = len(jax.devices())
    mesh = make_mesh((n,), ("data",))
    shape = list(reduced_domain(spec, scale))
    shape[0] = (shape[0] + n - 1) // n * n
    fn, pspec = make_distributed_stencil(spec, mesh, {0: "data"},
                                         tuple(shape), t_total, t_block)
    x = init_domain(spec, tuple(shape))
    from jax.sharding import NamedSharding
    xs = jax.device_put(x, NamedSharding(mesh, pspec))
    t0 = time.time()
    y = fn(xs)
    y.block_until_ready()
    dt = time.time() - t0
    want = ref.reference(x, spec, t_total)
    err = float(jnp.abs(y - want).max())
    print(f"[stencil-dist] {name:11s} domain={tuple(shape)} shards={n} "
          f"t={t_total}(x{t_block}) {dt*1e3:.0f}ms maxerr={err:.2e}",
          flush=True)
    assert err < 1e-4
    return y


QUICKSTART = """\
quick start (compile once, run many — any tap set):
  from repro.api import Boundary, compile_stencil, define_stencil
  spec = define_stencil([((0,0),0.6), ((0,1),0.1), ...])  # or get("j2d5pt")
  prog = compile_stencil(spec, x.shape, t=6,
                         boundary=Boundary.periodic())
  y = prog.run(x, T=64)     # or prog.apply(x) / prog.run_batched(xs, T)

custom stencils from the CLI (derived cost model printed):
  --taps '[[[0,0],0.6],[[0,1],0.1],[[0,-1],0.1],[[1,0],0.1],[[-1,0],0.1]]'
  --spec-json my_stencil.json   # {"taps": [...], "name": ..., ...}

sharded execution over a device mesh (docs/sharding.md):
  --mesh 2x4                    # one deep-halo exchange per temporal block
  (CPU hosts fake the device count automatically)

legacy ops.ebisu_stencil / sweep.run_sweeps are deprecated shims over
compiled programs (policy in README.md)."""


def main():
    ap = argparse.ArgumentParser(
        epilog=QUICKSTART,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--stencil", default="all",
                    help="Table-2 names (comma-separated) or 'all'")
    ap.add_argument("--taps", default=None,
                    metavar="'[[[0,0],0.6],...]'",
                    help="define a custom stencil from a JSON tap list")
    ap.add_argument("--spec-json", default=None, metavar="FILE",
                    help="define a custom stencil from a JSON spec file")
    ap.add_argument("--normalize", action="store_true",
                    help="rescale --taps coefficients to sum to 1")
    ap.add_argument("--name", default=None,
                    help="name for the --taps stencil")
    ap.add_argument("--system", default=None, metavar="NAME",
                    help="run a coupled multi-field system (gray-scott | "
                         "fdtd-acoustic | advection-diffusion) — "
                         "docs/systems.md")
    ap.add_argument("--t", type=int, default=None)
    ap.add_argument("--scale", type=int, default=64)
    ap.add_argument("--boundary", type=parse_boundary, default=None,
                    metavar="dirichlet[:v]|periodic|reflect|neumann[:flux]",
                    help="boundary condition (default zero Dirichlet)")
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    metavar="N|ZxY",
                    help="device mesh for run_sharded (axis k shards dim k);"
                         " CPU hosts fake the device count automatically")
    ap.add_argument("--T", type=int, default=None, dest="total_t",
                    help="total steps for --mesh/--checkpoint-dir runs "
                         "(default 2*t+1)")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="run as a checkpointed resumable campaign into DIR"
                         " (docs/resilience.md)")
    ap.add_argument("--resume", default="auto",
                    choices=("auto", "never", "always"),
                    help="campaign resume mode (default auto: pick up the "
                         "newest good checkpoint in --checkpoint-dir)")
    ap.add_argument("--every", type=int, default=1, metavar="N",
                    help="temporal blocks per campaign leg (default 1)")
    ap.add_argument("--kill-after-leg", type=int, default=None, metavar="K",
                    help="SIGKILL the process after leg K's checkpoint "
                         "lands (crash-restart testing)")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="np.save the final field to FILE")
    args = ap.parse_args()
    use_compile_cache()
    if args.taps and args.spec_json:
        ap.error("--taps and --spec-json are mutually exclusive")
    if args.mesh and args.distributed:
        ap.error("--mesh (run_sharded) and --distributed (jnp reference "
                 "scheme) are mutually exclusive")
    if args.checkpoint_dir and args.distributed:
        ap.error("--checkpoint-dir (resumable campaigns) drives compiled "
                 "programs; --distributed is the jnp reference scheme")
    if args.kill_after_leg is not None and not args.checkpoint_dir:
        ap.error("--kill-after-leg needs --checkpoint-dir")
    if args.mesh:
        # must happen before the backend initializes (main() is the first
        # device use); no-op when a device-count flag is already set, and
        # the forced count only affects the host CPU platform
        import math

        from repro.launch.mesh import ensure_fake_devices
        ensure_fake_devices(math.prod(args.mesh))
    if args.system:
        if args.taps or args.spec_json or args.mesh or args.distributed \
                or args.checkpoint_dir:
            ap.error("--system runs single-device fused system programs; "
                     "it composes with --t/--T/--scale/--boundary only")
        run_system_cli(args.system, t=args.t, scale=args.scale,
                       boundary=args.boundary, total_t=args.total_t)
        return
    if args.taps or args.spec_json:
        if args.distributed:
            ap.error("--distributed drives the Table-2 suite; custom specs "
                     "run single-device (for now)")
        spec = (define_stencil(parse_taps(args.taps),
                               normalize=args.normalize, name=args.name)
                if args.taps else spec_from_json(args.spec_json))
        if args.checkpoint_dir:
            run_campaign_cli(
                spec, checkpoint_dir=args.checkpoint_dir,
                mesh_shape=args.mesh, t=args.t, scale=args.scale,
                boundary=args.boundary, total_t=args.total_t,
                every=args.every, resume=args.resume,
                kill_after_leg=args.kill_after_leg, out=args.out)
        elif args.mesh:
            print(cost_summary_line(spec), flush=True)
            run_sharded(spec, args.mesh, t=args.t, scale=args.scale,
                        boundary=args.boundary, total_t=args.total_t)
        else:
            run_single(spec, t=args.t, scale=args.scale,
                       boundary=args.boundary, summary=True)
        return
    names = list(TABLE2) if args.stencil == "all" else args.stencil.split(",")
    for n in names:
        if args.checkpoint_dir:
            run_campaign_cli(
                n, checkpoint_dir=args.checkpoint_dir, mesh_shape=args.mesh,
                t=args.t, scale=args.scale, boundary=args.boundary,
                total_t=args.total_t, every=args.every, resume=args.resume,
                kill_after_leg=args.kill_after_leg, out=args.out)
        elif args.mesh:
            run_sharded(n, args.mesh, t=args.t, scale=args.scale,
                        boundary=args.boundary, total_t=args.total_t)
        elif args.distributed:
            run_distributed(n, scale=args.scale)
        else:
            run_single(n, t=args.t, scale=args.scale,
                       boundary=args.boundary)


if __name__ == "__main__":
    main()
