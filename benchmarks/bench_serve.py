"""``serve/`` bench family: the request path, measured end to end.

What coalescing buys is the measured ``run_batched`` win amortized over
a *request stream*: one vmapped dispatch per shape bucket instead of one
dispatch per request.  Rows drive the real :class:`ServiceCore` (real
monotonic clock — latencies here are wall time, unlike the CLI driver's
simulated clock) over a fixed seeded burst of requests:

    serve/coalesced-<spec>    us_per_call = wall us per request, from
        the first submit to the drained stream
        derived: rps|batches|note
    serve/unbatched-<spec>    the same burst at max_batch=1 (every
        request dispatches alone — the no-coalescing control)
    serve/degraded-<spec>     the same burst under injected faults
        (forced evictions + OOM above half width): the ladder must keep
        serving at reduced throughput, never stall — the row exists to
        track the *cost of degrading*, not to win

Interpret-mode wall time on a shared CPU is noisy (see DESIGN.md §14);
the tracked quantities are the coalesced/unbatched ratio and the
degraded row's completion — both load-resistant.  Rows are persisted to
``BENCH_serve.json`` by ``benchmarks/run.py``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.serve.faults import FaultConfig, FaultInjector
from repro.serve.stencil_service import (ServeRequest, ServiceConfig,
                                         ServiceCore)
from repro.stencils.data import init_domain
from repro.core.stencil_spec import get

# one 2-D case: service-path benches re-dispatch N_REQ requests per row,
# so the budget goes to stream length rather than spec breadth.  The
# shape/T/width regime is the one where the batched win was measured
# (PR 3's program/batch4 row): compute-bound enough that one vmapped
# dispatch beats a dispatch per request.  Width matters: vmap over the
# interpret-mode kernel scales superlinearly on CPU, so the raw win
# decays with width (measured here: 1.9x at 2, 1.6x at 4, gone by 8) —
# which is exactly why ``ServiceConfig.batch_widths`` is tunable.  T
# matters too: the request path itself is Python-bound (~constant
# us/request of submit/poll/resolve machinery either way), so T must be
# deep enough that compute dominates machinery or the ratio drowns —
# T=12 measures near-parity, T=24 a stable ~1.3x stream-level win.
CASE = ("j2d5pt", (128, 128), 24)    # name, shape, total_t
N_REQ = 24
MAX_BATCH = 4


def _drive(core: ServiceCore, spec, shape, total_t: int):
    """Submit the seeded burst, drain, return the resolved tickets and
    the stream's wall seconds.

    Inputs are materialized BEFORE the first submit: the timed stream
    runs first submit -> drained, and building domains inside it would
    add a constant per-request cost that drowns the batched-vs-solo
    delta."""
    fields = [init_domain(spec, shape, seed=i) for i in range(N_REQ)]
    t0 = time.perf_counter()
    tks = [core.submit(ServeRequest(spec, x, total_t=total_t))
           for x in fields]
    core.drain()
    jax.block_until_ready([tk.value for tk in tks if tk.ok])
    return tks, time.perf_counter() - t0


def _row(label: str, core: ServiceCore, tickets, seconds: float) -> tuple:
    stats = core.stats()
    n_ok = sum(1 for tk in tickets if tk.ok)
    assert all(tk.done for tk in tickets), f"{label}: unresolved tickets"
    rps = len(tickets) / seconds
    return (f"serve/{label}", 1e6 / rps,
            f"rps={rps:.1f}|"
            f"batches={stats.get('batches', 0)}|"
            f"ok={n_ok}/{len(tickets)}|"
            f"note=real-clock-request-stream")


def _best_rows(scenarios, spec, shape, total_t: int,
               repeats: int = 3) -> list:
    """Best-of-N over whole request streams, with the repeats
    INTERLEAVED across scenarios (same estimator as ``common.time_fn``:
    shared-CPU contamination is one-sided, so each scenario's
    minimum-elapsed stream is its least-contaminated one — and
    interleaving means a load burst hits all scenarios, not just
    whichever one was running, keeping the tracked ratio honest)."""
    best = {}
    for _ in range(repeats):
        for label, make_core, check in scenarios:
            core = make_core()
            tks, seconds = _drive(core, spec, shape, total_t)
            if check is not None:
                check(core, tks)
            row = _row(label, core, tks, seconds)
            if label not in best or row[1] < best[label][1]:
                best[label] = row
    return [best[label] for label, _, _ in scenarios]


def rows():
    name, shape, total_t = CASE
    spec = get(name)

    def fresh(max_batch: int, faults=None) -> ServiceCore:
        # window 0: every poll dispatches what has arrived — the burst
        # is fully enqueued before the first drain pass, so coalescing
        # still forms full batches
        return ServiceCore(ServiceConfig(max_batch=max_batch,
                                         batch_window_ms=0.0,
                                         max_queue=4 * N_REQ,
                                         max_inflight_per_tenant=4 * N_REQ),
                           faults=faults)

    # degraded mode: every batch wider than half OOMs, 30% of dispatches
    # hit an eviction race — the ladder narrows and retries but serves.
    # NOTE the eviction faults clear RUNNER_CACHE, so the degraded row
    # legitimately pays re-jit costs — that IS the degraded mode.
    def degraded_faults() -> FaultInjector:
        return FaultInjector(FaultConfig(seed=0, evict_rate=0.3,
                                         oom_batch_limit=MAX_BATCH // 2))

    # warm every dispatch width each scenario reaches (bench protocol:
    # steady-state serving, not first-compile) — the degraded warm pass
    # replays the same seeded fault sequence, so the ladder's narrower
    # widths and the solo path compile outside timing too.  It runs
    # FIRST: its injected evictions clear the runner cache, which would
    # un-warm anything warmed before it.
    for warm in (fresh(MAX_BATCH, faults=degraded_faults()),
                 fresh(MAX_BATCH), fresh(1)):
        _drive(warm, spec, shape, total_t)

    # the degraded row only earns its keep if every request resolved OK
    def _all_ok(core, tks):
        assert all(tk.ok for tk in tks), "degraded run dropped requests"
        s = core.stats()
        _all_ok.extra = (f"splits={s.get('ladder_splits', 0)}|"
                         f"retries={s.get('retries', 0)}|"
                         f"note=fault-injected-ladder-kept-serving")

    out = _best_rows(
        [(f"coalesced-{name}-T{total_t}",
          lambda: fresh(MAX_BATCH), None),
         (f"unbatched-{name}-T{total_t}",
          lambda: fresh(1), None),
         (f"degraded-{name}-T{total_t}",
          lambda: fresh(MAX_BATCH, faults=degraded_faults()), _all_ok)],
        spec, shape, total_t)
    r = out[-1]
    out[-1] = (r[0], r[1],
               r[2].replace("note=real-clock-request-stream",
                            _all_ok.extra))
    return out


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(rows())
