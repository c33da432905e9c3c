"""The readers of the metrics the program's own instrumentation feeds:
``program.pad_crop_share`` on a hand-made trace named by the program's
``op_phases``, and ``entry.build_s`` from ``repro.telemetry``; both find
nothing where the program lacks the instrumentation."""
import sys

import jax
import pytest

from bench import harness
from bench import profile_reduce as pr
from bench.tests import tiny
from repro.api.program import StencilProgram

DEV = "/device:TPU:0"
KERNEL = {"long_name": 'custom-call(...), '
                       'custom_call_target="tpu_custom_call"'}


def _ctx(trace, domain=(27, 100)):
    bench, cell, config, traffic, _ = tiny.cell("j2d5pt.campaign")
    config["domain"] = list(domain)
    return harness.Context(cell, config, traffic, {}, trace, {}, {})


def _program(ctx):
    from bench.generator import spec_for
    from repro.api import compile_stencil

    return compile_stencil(spec_for(ctx.config), tuple(ctx.config["domain"]))


def _named_ops(phases):
    """One instruction of each scope, as a trace names it: a pad of 1 s,
    a crop of 1 s, two launches of 4 s, 2 s idle, a 1 s op in no scope."""
    pick = {}
    for name, phase in sorted(phases.items()):
        pick.setdefault(phase, []).append(name)
    sweep = pick["stencil.sweep"]
    return [pr.Op(f"%{pick['stencil.pad'][0]} = f32[] pad()", 0.0, 1.0, {}),
            pr.Op(f"%{sweep[0]} = f32[] custom-call()", 1.0, 5.0, KERNEL),
            pr.Op(f"%{sweep[-1]} = f32[] custom-call()", 5.0, 9.0, KERNEL),
            pr.Op(f"%{pick['stencil.crop'][0]} = f32[] slice()", 9.0, 10.0,
                  {}),
            pr.Op("%copy.99 = f32[] copy()", 12.0, 13.0, {})]


def test_pad_crop_share_on_a_hand_made_trace():
    read = harness.metric_reader("program.pad_crop_share")
    ctx = _ctx(None)
    steps = int(ctx.traffic["steps"])
    phases = _program(ctx).op_phases(steps)
    ctx.trace = pr.Trace((0.0, 15.0), {DEV: _named_ops(phases)}, [])
    # pad + crop = 2 s of 11 s busy
    assert read(ctx) == pytest.approx(100.0 * 2.0 / 11.0)
    assert harness.metric_reader("program.non_kernel_share")(ctx) == \
        pytest.approx(100.0 * 3.0 / 11.0)


def test_pad_crop_share_finds_nothing_it_cannot_name(monkeypatch):
    read = harness.metric_reader("program.pad_crop_share")
    # launches that are not the runner's (the map is of another executable)
    ops = [pr.Op("%pad.5 = f32[] pad()", 0.0, 1.0, {}),
           pr.Op("%ebisu2d_padded.12 = f32[] custom-call()", 1.0, 5.0,
                 KERNEL)]
    assert read(_ctx(pr.Trace((0.0, 6.0), {DEV: ops}, []))) is None
    assert read(_ctx(None)) is None
    # a program without op_phases, as before the scopes existed
    ctx = _ctx(None)
    ctx.trace = pr.Trace((0.0, 15.0), {DEV: _named_ops(
        _program(ctx).op_phases(int(ctx.traffic["steps"])))}, [])
    monkeypatch.delattr(StencilProgram, "op_phases")
    assert read(ctx) is None


def test_build_s_reads_the_program_counters(monkeypatch):
    from repro import telemetry

    read = harness.metric_reader("entry.build_s")
    ctx = _ctx(None, domain=(36, 140))
    prog = _program(ctx)
    x = jax.numpy.ones(prog.shape, jax.numpy.float32)
    prog.run(x, int(ctx.traffic["steps"])).block_until_ready()
    snap = telemetry.snapshot()
    assert snap["builds"] >= 1
    assert read(ctx) == pytest.approx(snap["compile_s"] + snap["build_s"])
    assert read(ctx) > 0
    # a program without the counters
    import repro
    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert read(ctx) is None
