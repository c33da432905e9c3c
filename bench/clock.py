"""The program's host spans on the device's clock, read from a profiler trace.

In a trace of a TPU host the device's events and the host's events are
stamped by two clocks that disagree by a millisecond or two, more than the
gaps between calls.  Both sides name each execution of a compiled module by
its ``run_id``: the device's ``XLA Modules`` events, and on the host
``DoEnqueueProgram`` (the enqueue) and ``CompleteCallbacks`` (the completion
callbacks).  Causality bounds the shift that puts device times on the host's
clock: every module starts after its enqueue and ends before its
callbacks.  The shift taken is the smallest one that puts every module start
at its enqueue or later.

On that clock the device's idle time in the window (the benchmark's host
span ``window``) is split by the program's own ``stencil.*`` spans: idle
time under a ``stencil.run``, ``stencil.run_batched`` or
``stencil.run_sharded`` span is the program's part of the per-call gap, the
rest is the caller's and the runtime's.

    python3 -m bench.clock trace.xplane.pb

Nothing here imports the program under test, and nothing here changes what
``bench.profile_reduce`` reads: its window cut, idle gaps and readers stay
on the trace's own clocks.
"""
from __future__ import annotations

import dataclasses
import json
import sys

from bench.profile_reduce import OPS_LINE, WINDOW_SPAN, gaps, length, union

PROGRAM_PREFIX = "stencil."
ENTRY_SPANS = ("stencil.run", "stencil.run_batched", "stencil.run_sharded")
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"


@dataclasses.dataclass
class Span:
    name: str
    start: float          # seconds, host clock
    end: float
    args: dict


@dataclasses.dataclass
class Clocks:
    """What a trace says about both clocks, in seconds, uncut."""

    window: tuple[float, float]                         # host clock
    ops: dict[str, list[tuple[float, float]]]           # device clock
    modules: dict[str, dict[int, tuple[float, float]]]  # device clock
    enqueue: dict[int, float]                           # host clock
    complete: dict[int, float]                          # host clock
    spans: list[Span]                                   # host clock

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def offset_bounds(self) -> tuple[float, float]:
        """``(lo, hi)``: every shift in it keeps each module between its
        enqueue and its completion callbacks (``hi`` is infinite where no
        completion was recorded)."""
        lo, hi = None, float("inf")
        for runs in self.modules.values():
            for run_id, (start, end) in runs.items():
                if run_id in self.enqueue:
                    s = self.enqueue[run_id] - start
                    lo = s if lo is None else max(lo, s)
                if run_id in self.complete:
                    hi = min(hi, self.complete[run_id] - end)
        if lo is None:
            raise ValueError("no module execution matches a host enqueue "
                             "by run_id")
        return lo, hi

    @property
    def offset_s(self) -> float:
        """The smallest shift that puts every module start at its enqueue
        or later: add it to a device time to read the host's clock."""
        return self.offset_bounds()[0]

    def idle(self, device: str) -> list[tuple[float, float]]:
        """The device's idle stretches of the window, on the host clock."""
        off = self.offset_s
        return gaps([(s + off, e + off) for s, e in self.ops[device]],
                    *self.window)

    def idle_share(self) -> float:
        """Idle over the window on the host clock, averaged over chips."""
        idle = [length(self.idle(d)) for d in self.ops]
        return sum(idle) / len(idle) / self.window_s

    def entry_idle_s(self, device: str) -> float:
        """Idle time of ``device`` that lies under an entry span."""
        under = union((sp.start, sp.end) for sp in self.spans
                      if sp.name in ENTRY_SPANS)
        return sum(length([(max(s, us), min(e, ue)) for us, ue in under])
                   for s, e in self.idle(device))

    def entry_idle_share(self) -> float | None:
        """:meth:`entry_idle_s` over the window, averaged over chips;
        ``None`` where the trace holds no entry span."""
        if not any(sp.name in ENTRY_SPANS for sp in self.spans):
            return None
        idle = [self.entry_idle_s(d) for d in self.ops]
        return sum(idle) / len(idle) / self.window_s


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def load(path: str) -> Clocks:
    """Read ``path`` with ``jax.profiler.ProfileData``: the TPU planes'
    operations and module executions, the host's enqueues, completions and
    ``stencil.*`` spans, and the last ``window`` span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules = {}, {}
    enqueue, complete, spans, windows = {}, {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = {
                        _stats(ev)["run_id"]: (
                            ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events if "run_id" in _stats(ev)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    end = (ev.start_ns + ev.duration_ns) * 1e-9
                    if ev.name == WINDOW_SPAN:
                        windows.append((start, end))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        spans.append(Span(ev.name, start, end, _stats(ev)))
                    elif ev.name in (ENQUEUE, COMPLETE):
                        run_id = _stats(ev).get("run_id")
                        if run_id is not None:
                            side = enqueue if ev.name == ENQUEUE else complete
                            side[run_id] = min(side.get(run_id, start), start)
    if not windows:
        raise RuntimeError(f"{path}: no '{WINDOW_SPAN}' host span")
    spans.sort(key=lambda sp: sp.start)
    return Clocks(windows[-1], ops, modules, enqueue, complete, spans)


def summary(clocks: Clocks) -> dict:
    lo, hi = clocks.offset_bounds()
    share = clocks.entry_idle_share()
    return {"offset_ms": 1e3 * lo, "offset_bound_ms": [1e3 * lo, 1e3 * hi],
            "window_s": clocks.window_s,
            "idle_share_pct": 100.0 * clocks.idle_share(),
            "entry_idle_share_pct": None if share is None else 100.0 * share,
            "spans": sorted({sp.name for sp in clocks.spans})}


if __name__ == "__main__":
    for p in sys.argv[1:]:
        print(p, json.dumps(summary(load(p))))
