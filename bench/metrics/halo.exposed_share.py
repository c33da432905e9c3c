"""Share of the campaign window in which a chip ran the sharded
executor's exchange with no sweep kernel beside it, averaged over chips:
the device time of the operations that the program's own scopes put in
``stencil.exchange`` (slab slices, ``ppermute``, ghost writes) that no
``stencil.sweep`` launch covers, over the window.  The scopes come from
``StencilProgram.op_phases`` of the campaign's ``run_sharded`` runner,
computed after the window, and a trace operation is matched to them by
its HLO instruction name.  Finds nothing for a configuration without a
mesh, for a program whose ``op_phases`` does not map a mesh program's
runner (one without ``repro.api.sharded.uses_kernel``, which would
compile the whole field for one chip instead), where the map has no
``stencil.exchange``, or where a kernel launch of the trace is not in the
map's ``stencil.sweep`` (the map would then be of another executable)."""
from bench.profile_reduce import is_mosaic, length, subtract


def instruction(op) -> str:
    """``%collective-permute-start.3 = ...`` -> ``collective-permute-start.3``."""
    return op.name.split(" = ", 1)[0].lstrip("%")


def phases_of(ctx) -> dict | None:
    from bench.generator import spec_for
    from repro.api import compile_stencil, sharded

    mesh = ctx.config.get("mesh")
    if ctx.traffic.get("kind") != "campaign" or not mesh \
            or not hasattr(sharded, "uses_kernel"):
        return None
    prog = compile_stencil(spec_for(ctx.config),
                           tuple(int(n) for n in ctx.config["domain"]),
                           mesh=tuple(mesh))
    return prog.op_phases(int(ctx.traffic["steps"]))


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    phases = phases_of(ctx)
    if not phases or "stencil.exchange" not in phases.values():
        return None
    shares = []
    for ops in tr.devices.values():
        kernels = [op for op in ops if is_mosaic(op)]
        if any(phases.get(instruction(op)) != "stencil.sweep"
               for op in kernels):
            return None
        exchange = [op.span for op in ops
                    if phases.get(instruction(op)) == "stencil.exchange"]
        exposed = subtract(exchange, [op.span for op in kernels])
        shares.append(100.0 * length(exposed) / tr.window_s)
    return sum(shares) / len(shares)
