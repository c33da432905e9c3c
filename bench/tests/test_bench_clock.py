"""The host-to-device clock offset and the idle time under the program's
entry spans, on hand-made clocks and on two traces recorded on a TPU v5
lite: the first, which the offset must leave read as it always was, and
one with the program's own spans and kernel names."""
from pathlib import Path

import pytest

from bench import clock, harness
from bench import profile_reduce as pr

DATA = Path(__file__).with_name("data")
PLAIN = DATA / "j2d5pt_2calls.xplane.pb"
SPANS = DATA / "j2d5pt_2calls_spans.xplane.pb"


def _j2d5pt_context(trace):
    """What a reader of the ``j2d5pt.campaign`` cell sees for ``trace``."""
    bench = harness.load_benchmark()
    cell = harness.cell_entry(bench, "j2d5pt.campaign")
    return harness.Context(cell, harness.load_json(harness.config_path(cell)),
                           harness.load_json(harness.traffic_path(cell)),
                           harness.peaks_for("TPU v5 lite"), trace, {}, {})


def _clocks(spans, ops=((1.0, 4.0), (5.0, 8.0)), window=(0.0, 10.0)):
    """Device ops on a clock 0.5 s behind the host's: module 1 runs 1-4
    (enqueued at 1.5, callbacks at 4.6), module 2 runs 5-8 (5.5, 8.9)."""
    return clock.Clocks(window, {"/device:TPU:0": list(ops)},
                        {"/device:TPU:0": {1: (1.0, 4.0), 2: (5.0, 8.0)}},
                        {1: 1.5, 2: 5.5}, {1: 4.6, 2: 8.9},
                        [clock.Span(n, s, e, {}) for n, s, e in spans])


def test_offset_is_the_smallest_causal_shift():
    c = _clocks([])
    assert c.offset_bounds() == pytest.approx((0.5, 0.6))
    assert c.offset_s == pytest.approx(0.5)
    # on the host clock the device runs 1.5-4.5 and 5.5-8.5
    assert c.idle("/device:TPU:0") == pytest.approx(
        [(0.0, 1.5), (4.5, 5.5), (8.5, 10.0)])
    assert c.idle_share() == pytest.approx(0.4)


def test_entry_idle_is_idle_under_an_entry_span():
    c = _clocks([("stencil.run", 1.2, 1.6), ("stencil.run", 5.0, 5.7),
                 ("stencil.build", 0.0, 10.0), ("stencil.run_batched",
                                                 9.0, 9.5)])
    # 1.2-1.5, 5.0-5.5 and 9.0-9.5 are idle under an entry span
    assert c.entry_idle_s("/device:TPU:0") == pytest.approx(1.3)
    assert c.entry_idle_share() == pytest.approx(0.13)
    assert c.entry_idle_share() <= c.idle_share()
    assert _clocks([("stencil.build", 0.0, 10.0)]).entry_idle_share() is None


def test_offset_needs_a_matching_enqueue():
    c = _clocks([])
    c.enqueue = {}
    with pytest.raises(ValueError, match="run_id"):
        c.offset_bounds()


def test_offset_of_the_recorded_trace():
    """The device's clock runs 1.616 ms behind the host's: module run 12
    starts on the device 1.616 ms before its enqueue on the host, and no
    module may end after its completion callbacks (2.207 ms)."""
    c = clock.load(str(PLAIN))
    lo, hi = c.offset_bounds()
    assert lo == pytest.approx(1.61579e-3, abs=1e-9)
    assert hi == pytest.approx(2.207015e-3, abs=1e-9)
    assert c.window_s == pytest.approx(0.101962721, abs=1e-9)
    assert c.idle_share() == pytest.approx(0.021268763, abs=1e-8)
    assert c.spans == [] and c.entry_idle_share() is None


def test_profile_reduce_readings_of_the_recorded_trace_stand():
    """The four per-layer readers, ``top_ops`` and ``idle_gaps`` read the
    first recorded trace on its own clocks, as they always have."""
    tr = pr.load(str(PLAIN))
    ctx = _j2d5pt_context(tr)
    want = {"device_idle_share.campaign": 3.3424931843472283,
            "program.non_kernel_share": 2.625927526241684,
            "sweep2d.hbm_share": 17.040328352773752,
            "sweep3d.hbm_share": None}
    for name, value in want.items():
        got = harness.metric_reader(name)(ctx)
        assert got == (None if value is None else pytest.approx(value,
                                                                rel=1e-12))
    assert tr.top_ops() == [["ebisu2d_padded", pytest.approx(0.095966651)],
                            ["slice", pytest.approx(0.001687148)],
                            ["pad", pytest.approx(0.000900825)]]
    assert tr.idle_gaps() == [["block", pytest.approx(0.002319209)],
                              ["block", pytest.approx(0.001088862)]]


def test_program_spans_of_the_recorded_trace():
    """Two ``run(y, 120)`` calls of j2d5pt traced on a TPU v5 lite with
    the program's own spans (``bench/tests/record_trace.py``): one
    ``stencil.run`` span a call, numbered in order, and about 0.27 ms of
    device idle a call under them on the aligned clock."""
    c = clock.load(str(SPANS))
    lo, hi = c.offset_bounds()
    assert lo == pytest.approx(1.347224e-3, abs=1e-9)
    assert hi == pytest.approx(1.8942e-3, abs=1e-9)
    assert [(sp.name, sp.args) for sp in c.spans] == [
        ("stencil.run", {"call": 3}), ("stencil.run", {"call": 4})]
    assert c.entry_idle_share() == pytest.approx(0.0052497995, abs=1e-9)
    assert c.entry_idle_share() <= c.idle_share()
    assert c.idle_share() == pytest.approx(0.0190090678, abs=1e-9)


def test_kernel_names_of_the_recorded_trace():
    """The launches carry the kernel's stable name, and the Mosaic rule of
    ``profile_reduce`` still finds every one."""
    tr = pr.load(str(SPANS))
    (dev,) = tr.devices
    assert len(tr.kernel_ops(dev)) == 24
    assert all(pr.short_name(op.name) == "ebisu2d_t10"
               for op in tr.kernel_ops(dev))
    assert [name for name, _ in tr.top_ops()] == ["ebisu2d_t10", "slice",
                                                  "pad"]


def test_pad_crop_share_of_the_recorded_trace(monkeypatch):
    """With the runner's phases as the chip's compile names them (checked
    for the described v5e in ``tests/test_chip_compile.py``), the pad and
    the crop are all the device time outside the kernel."""
    from repro.api.program import StencilProgram

    phases = {"pad.5": "stencil.pad", "slice.23": "stencil.crop",
              **{f"ebisu2d_t10.{i}": "stencil.sweep" for i in range(12, 24)}}
    monkeypatch.setattr(StencilProgram, "op_phases",
                        lambda self, total_t: phases)
    ctx = _j2d5pt_context(pr.load(str(SPANS)))
    share = harness.metric_reader("program.pad_crop_share")(ctx)
    assert share == pytest.approx(2.6036914616, abs=1e-8)
    assert share == pytest.approx(
        harness.metric_reader("program.non_kernel_share")(ctx), abs=1e-9)
