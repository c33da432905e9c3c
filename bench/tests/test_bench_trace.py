"""The trace reduction: interval arithmetic on hand-made operations, and the
whole reduction on a small trace recorded on a TPU v5 lite."""
from pathlib import Path

import pytest

from bench import profile_reduce as pr

DATA = Path(__file__).with_name("data")


def op(name, start, end, **stats):
    return pr.Op(name, start, end, stats)


KERNEL = {"long_name": "custom-call(...), custom_call_target=\"tpu_custom_call\""}


def test_union_and_subtract():
    assert pr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert pr.length([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert pr.subtract([(0, 10)], [(1, 2), (5, 7)]) == [(0, 1), (2, 5),
                                                          (7, 10)]
    assert pr.gaps([(1, 2), (1.5, 3)], 0, 4) == [(0, 1), (3, 4)]


def test_trace_shares():
    ops = [op("fusion.1", 0.0, 1.0), op("sweep", 1.0, 5.0, **KERNEL),
           op("sweep", 6.0, 9.0, **KERNEL),
           op("collective-permute-start.2", 8.0, 9.5)]
    tr = pr.Trace((0.0, 10.0), {"/device:TPU:0": ops},
                  [("block", 5.0, 6.0), ("dispatch", 9.5, 10.0)])
    dev = "/device:TPU:0"
    assert tr.busy_s(dev) == pytest.approx(8.5)
    assert tr.idle_share() == pytest.approx(0.15)
    assert len(tr.kernel_ops(dev)) == 2
    assert tr.kernel_s(dev) == pytest.approx(7.0)
    assert tr.exposed_collective_s(dev) == pytest.approx(0.5)
    assert tr.idle_gaps() == [["block", 1.0], ["dispatch", 0.5]]
    assert tr.top_ops(1) == [["sweep", 7.0]]


def test_recorded_trace_of_two_j2d5pt_calls():
    """Two ``run(y, 120)`` calls of j2d5pt at 8352^2 traced on a TPU v5
    lite: per call one pad, twelve sweep-kernel launches (t=10) and one
    crop, inside the host span ``window``."""
    tr = pr.load(str(DATA / "j2d5pt_2calls.xplane.pb"))
    (dev,) = tr.devices
    assert dev == "/device:TPU:0"
    assert tr.window_s == pytest.approx(0.101962721, abs=1e-9)
    assert tr.busy_s(dev) == pytest.approx(0.098554624, abs=1e-9)
    assert len(tr.kernel_ops(dev)) == 24
    assert tr.kernel_s(dev) == pytest.approx(0.095966651, abs=1e-9)
    assert tr.exposed_collective_s(dev) == 0
    assert [name for name, _ in tr.top_ops()] == ["ebisu2d_padded", "slice",
                                                  "pad"]
    gaps = tr.idle_gaps()
    assert gaps[0][0] == "block" and gaps[0][1] == pytest.approx(0.002319209,
                                                                 abs=1e-9)
    assert all(g[1] >= pr.MIN_GAP_S for g in gaps)
