"""Prove the EBISU stencil path runs natively on a TPU.

    python chip_smoke.py              # one chip: 2-D, 3-D and service phases
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

Drives the public entry points at the Table-2 published domains with the
planner's own depth and tile — ``compile_stencil(spec, domain)`` then
``StencilProgram.run`` — with every kernel lowered by Mosaic (a program
that fell back to the interpreter fails the run), and checks each result
against the plain oracle ``kernels.ref.reference``.  Inputs are uniform
random fields made on the device from a fixed seed.

Every phase must pass: an error is not caught, and the script exits
non-zero without the final line.  The last line of standard output is
one JSON object naming the device JAX reports.  Times printed are
informational wall-clock readings of one warm call each, not a
benchmark.  Without a TPU (including ``JAX_PLATFORMS=cpu``) the script
exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

TOL = 1e-4                      # what stencil_run.run_single asserts


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def field(shape, seed: int):
    import jax
    return jax.random.uniform(jax.random.PRNGKey(seed), shape)


def maxerr(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.abs(a - b).max())


def stencil_phase(name: str, seed: int) -> None:
    """The published domain through ``compile_stencil`` + ``run``:
    ``T = 4·t`` steps, i.e. four chained sweeps at the planner's depth."""
    from repro.api import compile_stencil
    from repro.core.stencil_spec import get
    from repro.kernels import ref

    spec = get(name)
    prog = compile_stencil(spec, spec.domain)
    check(prog.interpret is False, f"{prog} is not lowered by Mosaic")
    total = 4 * prog.t
    x = field(spec.domain, seed)
    t0 = time.perf_counter()
    y = prog.run(x, total).block_until_ready()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    prog.run(x, total).block_until_ready()
    warm_s = time.perf_counter() - t0
    err = maxerr(y, ref.reference(x, spec, total))
    p = prog.plan
    log(f"[{name}] domain={spec.domain} interpret={prog.interpret} "
        f"plan(t={p.t}, block={p.block}, lazy_batch={p.lazy_batch}, "
        f"buffers={p.parallelism.num_buffers}) "
        f"launch_block={prog.geometry()['block']} T={total} "
        f"mean_abs={float(abs(y).mean()):.6f} "
        f"maxerr={err:.3e} first_call_s={first_s:.2f} "
        f"warm_run_s={warm_s:.4f} (informational, not a benchmark)")
    check(err < TOL, f"{name} maxerr {err:.3e} >= {TOL}")


def service_phase(seed: int) -> None:
    """Requests of two Table-2 specs at the service's ``max_cells`` limit,
    coalesced into vmapped native dispatches, each answer checked."""
    from repro.core.stencil_spec import get
    from repro.kernels import ref
    from repro.serve.faults import SimClock
    from repro.serve.stencil_service import (ServeRequest, ServiceConfig,
                                             ServiceCore)

    config = ServiceConfig()
    core = ServiceCore(config, clock=SimClock())
    mix = (("j2d5pt", (2048, 2048)), ("j3d7pt", (64, 256, 256)))
    total = 8
    sent = []
    for i, (name, shape) in enumerate(m for m in mix for _ in range(3)):
        check(math.prod(shape) <= config.max_cells, f"{shape} too large")
        req = ServeRequest(get(name), field(shape, seed + i), total,
                           tenant=f"tenant{i % 2}")
        sent.append((req, core.submit(req)))
    core.drain()
    for prog, _ in core._programs.values():
        check(prog.interpret is False, f"{prog} is not lowered by Mosaic")
    answered = failed = 0
    for req, tk in sent:
        if not tk.ok:
            failed += 1
            log(f"[service] {req.spec.name} failed: {tk.error!r}")
            continue
        err = maxerr(tk.result(), ref.reference(req.x, req.spec, total))
        check(err < TOL, f"service {req.spec.name} maxerr {err:.3e}")
        answered += 1
    stats = core.stats()
    log(f"[service] requests={len(sent)} answered={answered} "
        f"failed={failed} batched_dispatches={stats.get('batches', 0)} "
        f"solo_dispatches={stats.get('solo_dispatches', 0)} "
        f"ladder_splits={stats.get('ladder_splits', 0)}")
    check(failed == 0 and answered == len(sent), "a request failed")
    # a batch that failed natively would be re-run solo and still answer
    check(stats.get("ladder_splits", 0) == 0
          and stats.get("solo_dispatches", 0) == 0,
          f"a coalesced batch fell back to solo dispatch: {stats}")


def sharded_phase(seed: int) -> None:
    """j3d7pt at its published domain over a 2x2 mesh (``run_sharded``:
    one deep-halo exchange per temporal block), compared with the same
    ``T`` steps of the one-chip program's ``run`` on device 0.  (The mesh
    program is planned per shard; its own ``run`` over the whole domain
    would launch the shard's tile and depth, which the one-chip VMEM
    budget does not fit.)"""
    import jax

    from repro.api import compile_stencil
    from repro.core.stencil_spec import get

    spec = get("j3d7pt")
    prog = compile_stencil(spec, spec.domain, mesh=(2, 2))
    single = compile_stencil(spec, spec.domain)
    check(single.interpret is False, f"{single} is not lowered by Mosaic")
    total = 4 * prog.t
    x = field(spec.domain, seed)
    t0 = time.perf_counter()
    y = prog.run_sharded(x.copy(), total).block_until_ready()
    first_s = time.perf_counter() - t0
    devices = {d.id for d in y.sharding.device_set}
    check(len(devices) == 4, f"result spans devices {devices}")
    one = single.run(jax.device_put(x, jax.devices()[0]), total)
    err = maxerr(jax.device_put(y, jax.devices()[0]), one)
    log(f"[sharded] j3d7pt domain={spec.domain} mesh=2x2 t={prog.t} "
        f"one_chip_t={single.t} T={total} devices={sorted(devices)} "
        f"mean_abs={float(abs(one).mean()):.6f} "
        f"maxerr_vs_one_chip={err:.3e} first_call_s={first_s:.2f}")
    check(err < TOL, f"sharded maxerr {err:.3e} >= {TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    log(f"[cache] {use_compile_cache()}")

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    log(f"[device] {dev.platform} {dev.device_kind} count={len(devices)}")
    if args.chips == 4:
        sharded_phase(args.seed)
    else:
        stencil_phase("j2d5pt", args.seed)
        stencil_phase("j3d7pt", args.seed + 1)
        service_phase(args.seed + 2)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
