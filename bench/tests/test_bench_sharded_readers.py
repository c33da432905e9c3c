"""The readers of the four-chip cell's per-layer metrics on hand-made
traces of several chips: ``halo.exposed_share`` (exchange time no sweep
launch covers, named by the program's ``op_phases``) and
``sweep3d.shard_hbm_share`` (the kernel's bytes for the chip's shard over
its time); both find nothing where there is nothing of theirs to read."""
import pytest

from bench import harness
from bench import profile_reduce as pr

KERNEL = {"long_name": 'custom-call(...), '
                       'custom_call_target="tpu_custom_call"'}
DEVS = ("/device:TPU:0", "/device:TPU:1")
# the runner's map as op_phases gives it: instruction name -> scope
PHASES = {"ebisu3d_t8.1": "stencil.sweep", "ebisu3d_t8.2": "stencil.sweep",
          "collective-permute-start.3": "stencil.exchange",
          "fusion.4": "stencil.exchange", "pad.5": "stencil.pad"}


def _ctx(trace, mesh=(2, 2), domain=(5120, 576, 384)):
    bench = harness.load_benchmark()
    cell = harness.cell_entry(bench, "j3d7pt-weak2x2.campaign")
    config = harness.load_json(harness.config_path(cell))
    traffic = harness.load_json(harness.traffic_path(cell))
    config.update(mesh=list(mesh) if mesh else None, domain=list(domain))
    return harness.Context(cell, config, traffic,
                           {"hbm_bytes_per_s": 819e9}, trace, {}, {})


def _op(name, start, end):
    stats = KERNEL if name.startswith("ebisu") else {}
    return pr.Op(f"%{name} = f32[] op()", start, end, stats)


def _exposed_reader(monkeypatch, phases=PHASES):
    read = harness.metric_reader("halo.exposed_share")
    monkeypatch.setitem(read.__globals__, "phases_of", lambda ctx: phases)
    return read


def test_exposed_share_counts_what_no_launch_covers(monkeypatch):
    read = _exposed_reader(monkeypatch)
    # chip 0: a round covered by a launch, one half covered, one bare;
    # chip 1: one bare round and a pad (no exchange)
    chip0 = [_op("ebisu3d_t8.1", 0.0, 4.0),
             _op("collective-permute-start.3", 1.0, 2.0),      # covered
             _op("fusion.4", 3.5, 4.5),                        # half
             _op("ebisu3d_t8.2", 5.0, 9.0),
             _op("collective-permute-start.3", 9.0, 10.0)]     # bare
    chip1 = [_op("ebisu3d_t8.1", 0.0, 4.0),
             _op("fusion.4", 4.0, 6.0),                        # bare
             _op("pad.5", 6.0, 7.0)]
    ctx = _ctx(pr.Trace((0.0, 10.0), dict(zip(DEVS, (chip0, chip1))), []))
    # chip 0: 0.5 + 1 of 10 s; chip 1: 2 of 10 s
    assert read(ctx) == pytest.approx((15.0 + 20.0) / 2)


def test_exposed_share_finds_nothing_it_cannot_name(monkeypatch):
    chip = [_op("ebisu3d_t8.1", 0.0, 4.0), _op("fusion.4", 4.0, 5.0)]
    trace = pr.Trace((0.0, 10.0), {DEVS[0]: chip}, [])
    # no map (no mesh, or a program that cannot map its mesh runner)
    assert _exposed_reader(monkeypatch, None)(_ctx(trace)) is None
    # a map with no exchange scope: the trapezoid of an older program
    no_exchange = {k: v for k, v in PHASES.items()
                   if v != "stencil.exchange"}
    assert _exposed_reader(monkeypatch, no_exchange)(_ctx(trace)) is None
    # a launch the map does not name: the map is of another executable
    other = [_op("ebisu3d_t8.9", 0.0, 4.0), _op("fusion.4", 4.0, 5.0)]
    assert _exposed_reader(monkeypatch)(_ctx(pr.Trace(
        (0.0, 10.0), {DEVS[0]: other}, []))) is None
    assert _exposed_reader(monkeypatch)(_ctx(None)) is None


def test_exposed_share_asks_no_map_of_a_program_without_one(monkeypatch):
    """Without ``repro.api.sharded.uses_kernel`` (a program from before
    the sweep kernel ran on shards) or without a mesh, the reader returns
    nothing and compiles nothing."""
    from repro.api import sharded

    read = harness.metric_reader("halo.exposed_share")
    trace = pr.Trace((0.0, 10.0), {DEVS[0]: [_op("fusion.4", 0.0, 1.0)]},
                     [])

    def refuse(*args, **kwargs):
        raise AssertionError("compiled a program")

    monkeypatch.setattr("repro.api.compile_stencil", refuse)
    assert read(_ctx(trace, mesh=None)) is None
    monkeypatch.delattr(sharded, "uses_kernel")
    assert read(_ctx(trace)) is None


def test_shard_hbm_share_on_two_chips():
    read = harness.metric_reader("sweep3d.shard_hbm_share")
    shard = 2560 * 288 * 384
    chips = {DEVS[0]: [_op("ebisu3d_t8.1", 0.0, 0.02),
                       _op("ebisu3d_t8.2", 0.03, 0.05)],     # 2 in 40 ms
             DEVS[1]: [_op("ebisu3d_t8.1", 0.0, 0.025)]}     # 1 in 25 ms
    ctx = _ctx(pr.Trace((0.0, 0.1), chips, []))
    want = [100.0 * n * 2 * shard * 4 / (s * 819e9)
            for n, s in ((2, 0.04), (1, 0.025))]
    assert read(ctx) == pytest.approx(sum(want) / 2)


def test_shard_hbm_share_finds_nothing_without_a_launch_on_each_chip():
    read = harness.metric_reader("sweep3d.shard_hbm_share")
    chips = {DEVS[0]: [_op("ebisu3d_t8.1", 0.0, 0.02)],
             DEVS[1]: [_op("fusion.4", 0.0, 0.02)]}          # the trapezoid
    assert read(_ctx(pr.Trace((0.0, 0.1), chips, []))) is None
    one = {DEVS[0]: chips[DEVS[0]]}
    assert read(_ctx(pr.Trace((0.0, 0.1), one, []), mesh=None)) is None
    assert read(_ctx(pr.Trace((0.0, 0.1), one, []), mesh=(2,),
                     domain=(64, 128))) is None
    assert read(_ctx(None)) is None
