"""The program's own instrumentation (``repro.telemetry``): host spans that
record only inside a profiler session, the build counters beside the
program caches' counters, and the device scopes read back by
``StencilProgram.op_phases``."""
import glob

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro import telemetry
from repro.api import Boundary, cache_stats, compile_stencil
from repro.core.stencil_spec import get
from repro.stencils.data import init_domain

# not a multiple of the tile in either dimension, so the chain pads and crops
SHAPE = (27, 100)


def _program(**kw):
    spec = get("j2d5pt")
    return compile_stencil(spec, SHAPE, t=2, interpret=True, **kw), \
        init_domain(spec, SHAPE, seed=1)


def _program_events(log_dir) -> list:
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(telemetry.PREFIX):
                    out.append((ev.name, {k: v for k, v in ev.stats}))
    return out


def test_spans_record_nothing_without_a_session(tmp_path):
    prog, x = _program()
    prog.run(x, 6).block_until_ready()          # no session: nothing kept
    with jax.profiler.trace(str(tmp_path / "empty")):
        pass
    assert _program_events(tmp_path / "empty") == []

    with jax.profiler.trace(str(tmp_path / "run")):
        prog.run(x, 8).block_until_ready()
        prog.run(x, 8).block_until_ready()
    events = _program_events(tmp_path / "run")
    runs = [args["call"] for name, args in events if name == "stencil.run"]
    assert len(runs) == 2 and runs[1] == runs[0] + 1
    (build,) = [args for name, args in events if name == "stencil.build"]
    assert build == {"entry": "run", "t": 8, "call": runs[0]}


def test_snapshot_holds_the_cache_counters():
    _program()
    snap = telemetry.snapshot()
    assert set(snap) == {"builds", "build_s", "compile_s", "exchange_rounds",
                         "halo_bytes", "caches"}
    assert snap["caches"] == cache_stats()
    assert snap["caches"]["programs"]["hits"] + \
        snap["caches"]["programs"]["misses"] >= 1


def test_a_runner_is_built_once_and_timed():
    before = telemetry.snapshot()
    prog, x = _program(boundary=Boundary.periodic())
    assert telemetry.snapshot()["compile_s"] > before["compile_s"]
    prog.run(x, 10).block_until_ready()
    first = telemetry.snapshot()
    assert first["builds"] - before["builds"] == 1
    assert first["build_s"] > before["build_s"]
    prog.run(x, 10).block_until_ready()
    assert telemetry.snapshot()["builds"] == first["builds"]
    prog.run_batched(jnp.stack([x, x]), 10).block_until_ready()
    assert telemetry.snapshot()["builds"] == first["builds"] + 1


def test_op_phases_put_the_chain_in_its_scopes():
    prog, _ = _program()
    phases = prog.op_phases(8)
    assert {"stencil.pad", "stencil.sweep", "stencil.crop"} <= \
        set(phases.values())
    assert set(phases.values()) <= {"stencil.pad", "stencil.sweep",
                                    "stencil.crop", "stencil.cast"}


def test_op_phases_of_a_repinned_chain():
    prog, _ = _program(boundary=Boundary.periodic())
    phases = set(prog.op_phases(4).values())
    assert {"stencil.repin", "stencil.sweep"} <= phases
    assert "stencil.pad" not in phases and "stencil.crop" not in phases


def test_op_phases_build_nothing():
    prog, _ = _program()
    before = telemetry.snapshot()
    prog.op_phases(12)
    after = telemetry.snapshot()
    assert after["builds"] == before["builds"]
    assert after["caches"]["runners"] == before["caches"]["runners"]


def test_phases_reads_the_outermost_program_scope():
    text = """
ENTRY %main.3 (x.1: f32[8,8]) -> f32[8,8] {
  %x.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %pad.5 = f32[16,128]{1,0} pad(%x.1, %c), metadata={op_name="jit(run)/stencil.pad/scatter" stack_frame_id=4}
  %ebisu2d_t4.7 = f32[16,128]{1,0} custom-call(%pad.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/stencil.sweep/jit(ebisu2d_padded)/pallas_call" stack_frame_id=7}
  ROOT %slice.9 = f32[8,8]{1,0} slice(%ebisu2d_t4.7), slice={[0:8], [0:8]}, metadata={op_name="jit(run)/stencil.crop/slice"}
}"""
    assert telemetry.phases(text) == {"pad.5": "stencil.pad",
                                      "ebisu2d_t4.7": "stencil.sweep",
                                      "slice.9": "stencil.crop"}

