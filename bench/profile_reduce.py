"""Reduce a profiler trace (``.xplane.pb``) to device intervals and host spans.

The reduction is the benchmark's yardstick for every per-layer metric read
from the device trace, so it lives here and not in the program:

* device operations are the events of each TPU plane's ``XLA Ops`` line;
* busy time is the union of those intervals inside the measured window
  (the host span ``window``), idle time is the rest of the window;
* the sweep kernel is the Mosaic custom call of the cell (see
  :func:`is_mosaic`): the rule reads what the compiler made of the
  launch, not the kernel function's name, so a rename does not lose it;
* collective time is the union of the collective operations' intervals,
  and its exposed part is what no other operation on that device covers;
* idle gaps are attributed to the benchmark's own host span that overlaps
  them most (``generate``, ``dispatch``, ``block``, ``submit``, ``await``).

Nothing here imports the program under test.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import Counter

HOST_SPANS = ("generate", "dispatch", "block", "submit", "await")
WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
MIN_GAP_S = 1e-6          # the trace's own rounding leaves ns-wide gaps
COLLECTIVE_PREFIXES = ("collective-permute", "all-reduce", "all-gather",
                       "reduce-scatter", "all-to-all", "send", "recv")


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint, sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of the union of ``a`` that no interval of ``b`` covers."""
    out, b = [], union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    return subtract([(lo, hi)], intervals)


@dataclasses.dataclass
class Op:
    name: str
    start: float          # seconds, on the trace's clock
    end: float
    stats: dict

    @property
    def span(self) -> tuple[float, float]:
        return self.start, self.end


def is_mosaic(op: Op) -> bool:
    """The sweep kernel's rule: an operation whose HLO is a custom call to
    ``tpu_custom_call``, which is what every Pallas kernel lowered by
    Mosaic becomes.  The trace names each operation by its HLO text
    (``%ebisu2d_padded.12 = f32[...] custom-call(...),
    custom_call_target="tpu_custom_call", ...``), so the rule reads the
    instruction the compiler emitted and holds whatever the kernel
    function is called."""
    return 'custom_call_target="tpu_custom_call"' in op.name or \
        "tpu_custom_call" in str(op.stats.get("long_name", ""))


def short_name(name: str) -> str:
    """``%ebisu2d_padded.12 = f32[...] custom-call(...)`` -> ``ebisu2d_padded``:
    the instruction's name without its number, to group launches."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def is_collective(op: Op) -> bool:
    return short_name(op.name).startswith(COLLECTIVE_PREFIXES)


@dataclasses.dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans, all in
    seconds on one clock, cut to the measured window."""

    window: tuple[float, float]
    devices: dict[str, list[Op]]
    spans: list[tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self, device: str) -> float:
        return length(op.span for op in self.devices[device])

    def mean_busy_s(self) -> float:
        return sum(map(self.busy_s, self.devices)) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s() / self.window_s

    def kernel_ops(self, device: str) -> list[Op]:
        return [op for op in self.devices[device] if is_mosaic(op)]

    def kernel_s(self, device: str) -> float:
        return length(op.span for op in self.kernel_ops(device))

    def exposed_collective_s(self, device: str) -> float:
        ops = self.devices[device]
        coll = [op.span for op in ops if is_collective(op)]
        other = [op.span for op in ops if not is_collective(op)]
        return length(subtract(coll, other))

    def top_ops(self, n: int = 10) -> list[list]:
        """The device operations that took most time, summed over chips
        and averaged per chip."""
        tot: Counter = Counter()
        for ops in self.devices.values():
            for op in ops:
                tot[short_name(op.name)] += op.end - op.start
        k = len(self.devices)
        return [[name, s / k] for name, s in tot.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The longest idle stretches of the first chip, each named by the
        host span that overlaps it most (``"none"`` when none does)."""
        dev = sorted(self.devices)[0]
        lo, hi = self.window
        out = []
        for s, e in gaps([op.span for op in self.devices[dev]], lo, hi):
            if e - s < MIN_GAP_S:
                continue
            best, cover = "none", 0.0
            for name, hs, he in self.spans:
                ov = min(e, he) - max(s, hs)
                if ov > cover:
                    best, cover = name, ov
            out.append([best, e - s])
        out.sort(key=lambda g: -g[1])
        return out[:n]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {paths}")
    return paths[0]


def _stats(event) -> dict:
    out = {}
    for item in event.stats:
        k, v = item
        out[k] = v
    return out


def load(path: str) -> Trace:
    """Read ``path`` with ``jax.profiler.ProfileData``, keep the TPU
    planes' operations and the benchmark's host spans, and cut both to the
    ``window`` span (the last one, where several were recorded)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[Op]] = {}
    spans, windows = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9,
                                  _stats(ev)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        windows.append((ev.start_ns * 1e-9,
                                        (ev.start_ns + ev.duration_ns) * 1e-9))
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    if not windows:
        raise RuntimeError(f"{path}: no '{WINDOW_SPAN}' host span")
    lo, hi = windows[-1]
    cut = {}
    for dev, ops in devices.items():
        cut[dev] = [dataclasses.replace(op, start=max(op.start, lo),
                                        end=min(op.end, hi))
                    for op in ops if min(op.end, hi) > max(op.start, lo)]
    return Trace((lo, hi), cut,
                 [(n, max(s, lo), min(e, hi)) for n, s, e in spans
                  if min(e, hi) > max(s, lo)])
