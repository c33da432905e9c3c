"""Share of the device's busy time spent in the executor's pad, crop and
casts, averaged over chips: the device time of the operations that the
program's own scopes put in ``stencil.pad``, ``stencil.crop`` or
``stencil.cast``, over busy time.  The scopes come from
``StencilProgram.op_phases`` of the campaign's runner, computed after the
window, and a trace operation is matched to them by its HLO instruction
name.  Finds nothing where the program has no ``op_phases``, for a sharded
configuration, or where a kernel launch of the trace is not in the map's
``stencil.sweep`` (the map would then be of another executable)."""
from bench.profile_reduce import is_mosaic, length

PHASES = ("stencil.pad", "stencil.crop", "stencil.cast")


def instruction(op) -> str:
    """``%pad.5 = f32[...] pad(...)`` -> ``pad.5``."""
    return op.name.split(" = ", 1)[0].lstrip("%")


def phases_of(ctx) -> dict | None:
    from bench.generator import spec_for
    from repro.api import compile_stencil

    if ctx.traffic.get("kind") != "campaign" or ctx.config.get("mesh"):
        return None
    prog = compile_stencil(spec_for(ctx.config),
                           tuple(int(n) for n in ctx.config["domain"]))
    if not hasattr(prog, "op_phases"):
        return None
    return prog.op_phases(int(ctx.traffic["steps"]))


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    phases = phases_of(ctx)
    if phases is None:
        return None
    shares = []
    for dev, ops in tr.devices.items():
        kernels = [op for op in ops if is_mosaic(op)]
        busy = tr.busy_s(dev)
        if not kernels or busy <= 0 or any(
                phases.get(instruction(op)) != "stencil.sweep"
                for op in kernels):
            return None
        part = length(op.span for op in ops
                      if phases.get(instruction(op)) in PHASES)
        shares.append(100.0 * part / busy)
    return sum(shares) / len(shares)
