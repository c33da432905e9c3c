"""The one general traffic generator: a traffic mix is a data file of
parameters, and ``traffic["kind"]`` picks how it drives the program.

``campaign`` — one caller advancing one field in a closed loop,
``y = prog.run(y, steps)`` back to back (``run_sharded`` for a configuration
with a ``mesh``).  Keys: ``steps``.

``service`` — independent clients in an open loop into ``StencilService``.
Keys: ``rate_per_s`` (Poisson arrivals), ``burst_size`` and
``burst_every_s`` (requests due at one instant), ``domains`` with
``domain_shares``, ``steps`` with ``step_shares``, ``tenants``,
``pool_per_domain`` (input fields made from the seed at set-up),
``check_per_group`` (answers compared per domain and step count) and
``grace_s`` (how long past the window an answer is waited for).

Every seed gets the same set of arrivals, sizes and step counts, in another
order, so that a seed changes the order of the work and not its amount.
Each driver warms up every shape it will use in ``prepare`` and times
nothing there; ``window`` measures; ``check`` compares what the window
produced with ``bench.reference``; ``release`` drops what the window held.
"""
from __future__ import annotations

import asyncio
import collections
import functools
import itertools
import math
import random
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from bench import reference


def prng_key(seed: int, salt: int = 0):
    """A key for any whole-number seed, wider than 32 bits included."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), salt)


@functools.lru_cache(maxsize=None)
def _field_fn(shape: tuple[int, ...], sharding):
    return jax.jit(lambda key: jax.random.uniform(key, shape, jnp.float32),
                   out_shardings=sharding)


def make_field(seed: int, shape, sharding, salt: int = 0):
    """A uniform [0, 1) float32 field made on the device from the seed."""
    return _field_fn(tuple(shape), sharding)(prng_key(seed, salt))


def spec_for(config: dict):
    """The program's stencil for ``config``, checked against the taps the
    configuration file states (those the reference runs)."""
    from repro.core.stencil_spec import get

    spec = get(config["stencil"])
    want = reference.taps_of(config)
    if tuple((tuple(o), float(w)) for o, w in spec.taps) != want:
        raise ValueError(f"{config['stencil']}: the program's taps "
                         f"{spec.taps} are not the configuration's {want}")
    return spec


def quantile_shares(n: int, values, shares, rng: random.Random) -> list:
    """``n`` draws of ``values`` in exact proportion to ``shares``
    (largest remainders), in an order drawn from ``rng``."""
    total = float(sum(shares))
    raw = [n * s / total for s in shares]
    counts = [int(r) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])[
            :n - sum(counts)]:
        counts[i] += 1
    out = [v for v, c in zip(values, counts) for _ in range(c)]
    rng.shuffle(out)
    return out


@functools.lru_cache(maxsize=None)
def _copy_fn():
    return jax.jit(lambda v: v + 0)


def copy(x):
    """A fresh device buffer holding ``x`` (kept past a donating call)."""
    return _copy_fn()(x)


class LowPrecisionReference:
    """The control: the plain reference in the program's place, computed in
    bfloat16, the precision below the float32 the configuration states.
    It answers the program's ``run`` and ``run_batched`` calls."""

    def __init__(self, config: dict):
        self.taps = reference.taps_of(config)

    def run(self, x, steps: int):
        return reference.run(x, self.taps, steps, jnp.bfloat16)

    def run_batched(self, xs, steps: int):
        return _batched_reference(xs, self.taps, steps)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _batched_reference(xs, taps, steps: int):
    return jax.vmap(lambda v: reference.run(v, taps, steps, jnp.bfloat16))(xs)


class Campaign:
    """One field of the configuration's domain advanced ``steps`` at a call,
    back to back, by one caller that waits for each call."""

    def __init__(self, config: dict, traffic: dict, devices, *,
                 control: bool = False):
        from repro.api import compile_stencil

        self.config = config
        self.steps = int(traffic["steps"])
        self.shape = tuple(int(n) for n in config["domain"])
        mesh = config.get("mesh")
        spec = spec_for(config)
        self.prog = compile_stencil(spec, self.shape,
                                    mesh=tuple(mesh) if mesh else None)
        if devices[0].platform == "tpu" and self.prog.interpret:
            raise RuntimeError(f"{self.prog} is not lowered by Mosaic")
        self.sharded = mesh is not None
        if self.sharded:
            self.sharding = NamedSharding(
                self.prog.mesh, PartitionSpec(*self.prog.mesh.axis_names))
            self.call = self.prog.run_sharded
        else:
            self.sharding = jax.sharding.SingleDeviceSharding(devices[0])
            self.call = self.prog.run
        if control:
            self.call = LowPrecisionReference(config).run
        self.cells = math.prod(self.shape)
        self.y = self.kept = None

    def describe(self) -> str:
        return (f"program t={self.prog.t} geometry={self.prog.geometry()} "
                f"interpret={self.prog.interpret} sharded={self.sharded}")

    def prepare(self, seed: int) -> None:
        """The field from the seed and two warm calls: the first loads or
        compiles the chain, the second gives the call's length."""
        y = make_field(seed, self.shape, self.sharding)
        y = self.call(y, self.steps).block_until_ready()
        t0 = time.perf_counter()
        self.y = self.call(y, self.steps).block_until_ready()
        self.call_s = time.perf_counter() - t0
        copy(self.y).block_until_ready()

    def window(self, seconds: float, rng: random.Random) -> dict:
        """Calls until ``seconds`` have passed; the window ends when the
        last call's result is ready.  One call, drawn from the seed among
        those that surely fall in the window, keeps its input and output
        for :meth:`check` (copied, where the call donates its input)."""
        k = rng.randint(1, max(1, int(seconds / (1.5 * self.call_s))))
        keep = copy if self.sharded else (lambda v: v)
        y, calls = self.y, 0
        self.y = None
        span = TraceAnnotation("window")
        span.__enter__()
        t0 = time.perf_counter()
        while True:
            calls += 1
            if calls == k:
                x_in = keep(y)
            with TraceAnnotation("dispatch"):
                y = self.call(y, self.steps)
            with TraceAnnotation("block"):
                y.block_until_ready()
            if calls == k:
                self.kept = (x_in, keep(y))
            if time.perf_counter() - t0 >= seconds and calls >= k:
                break
        window_s = time.perf_counter() - t0
        span.__exit__(None, None, None)
        del y
        work = calls * self.cells * self.steps
        return {"attempted": calls, "failed": 0, "start": t0,
                "gcells_per_s": work / window_s / 1e9,
                "info": {"calls": calls, "call_s_warm": self.call_s,
                         "checked_call": k, "cell_updates": work}}

    def counters(self) -> dict:
        return {}

    def release(self) -> None:
        self.y = None

    def check(self) -> dict:
        x_in, y_out = self.kept
        self.kept = None
        ref = reference.run(x_in, reference.taps_of(self.config), self.steps)
        return {"max_abs_err": float(reference.max_abs_err(y_out, ref))}


class Service:
    """Open-loop clients into ``StencilService`` with the default
    ``ServiceConfig``; each request is timed from its due time in the
    schedule to its result being ready on the device."""

    def __init__(self, config: dict, traffic: dict, devices, *,
                 control: bool = False):
        from repro.api import compile_stencil
        from repro.serve.stencil_service import ServiceConfig

        self.config = config
        self.traffic = traffic
        self.spec = spec_for(config)
        self.domains = [tuple(int(n) for n in d) for d in traffic["domains"]]
        self.step_counts = [int(t) for t in traffic["steps"]]
        self.service_config = ServiceConfig()
        self.compile_fn = compile_stencil
        if control:
            stand_in = LowPrecisionReference(config)
            self.compile_fn = lambda *args, **kwargs: stand_in
        self.sharding = jax.sharding.SingleDeviceSharding(devices[0])
        self.pool = {}
        self.kept = {}
        self.stats = {}

    def describe(self) -> str:
        return (f"service widths={self.service_config.widths()} "
                f"batch_window_ms={self.service_config.batch_window_ms} "
                f"rate_per_s={self.traffic['rate_per_s']}")

    def _core(self):
        from repro.serve.stencil_service import ServiceCore

        return ServiceCore(self.service_config, compile_fn=self.compile_fn)

    def prepare(self, seed: int) -> None:
        """The input pool from the seed, then every program and host-side
        operation the window can reach: each (domain, steps) bucket
        dispatched at every batch length up to ``max_batch``."""
        from repro.serve.stencil_service import ServeRequest

        n = int(self.traffic["pool_per_domain"])
        self.pool = {d: [make_field(seed, d, self.sharding, salt=i * 97 + j)
                         for j in range(n)]
                     for i, d in enumerate(self.domains)}
        core = self._core()
        for d in self.domains:
            for steps in self.step_counts:
                for size in range(1, self.service_config.max_batch + 1):
                    tickets = [core.submit(ServeRequest(
                        self.spec, self.pool[d][j % n], steps,
                        tenant=f"tenant{j}")) for j in range(size)]
                    for batch in core.poll(force=True):
                        core.dispatch(batch)
                    for tk in tickets:
                        tk.result().block_until_ready()

    def schedule(self, seconds: float, rng: random.Random) -> list:
        """``(due_s, domain, steps, tenant, pool_index)`` per request.  The
        Poisson arrivals and each burst get the mix's exact shares of
        domains, step counts and tenants, so that every seed offers the
        same work, in another order."""
        tr = self.traffic
        rate = float(tr["rate_per_s"])
        n = max(1, round(rate * seconds))
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        rng.shuffle(gaps)
        groups = [list(itertools.accumulate(gaps))]
        every = float(tr["burst_every_s"])
        k = 0
        while (k + 0.5) * every < seconds:
            groups.append([(k + 0.5) * every] * int(tr["burst_size"]))
            k += 1
        tenants = list(range(int(tr["tenants"])))
        sched = []
        for dues in groups:
            m = len(dues)
            sched += zip(dues,
                         quantile_shares(m, self.domains, tr["domain_shares"],
                                         rng),
                         quantile_shares(m, self.step_counts,
                                         tr["step_shares"], rng),
                         quantile_shares(m, tenants, [1] * len(tenants), rng),
                         (rng.randrange(int(tr["pool_per_domain"]))
                          for _ in range(m)))
        sched.sort(key=lambda r: r[0])
        return sched

    def window(self, seconds: float, rng: random.Random) -> dict:
        sched = self.schedule(seconds, rng)
        per = int(self.traffic["check_per_group"])
        sample = set()
        for d in self.domains:
            for s in self.step_counts:
                group = [i for i, r in enumerate(sched) if r[1:3] == (d, s)]
                sample.update(rng.sample(group, min(per, len(group))))
        return asyncio.run(self._drive(sched, sample, seconds))

    async def _drive(self, sched, sample, seconds: float) -> dict:
        from repro.serve.stencil_service import (Expired, Rejected,
                                                 ServeRequest,
                                                 StencilService)

        svc = StencilService(self.service_config)
        svc.core._compile = self.compile_fn
        core_submit, core_dispatch = svc.core.submit, svc.core.dispatch

        def submit(*a, **k):
            with TraceAnnotation("submit"):
                return core_submit(*a, **k)

        def dispatch(*a, **k):
            with TraceAnnotation("dispatch"):
                return core_dispatch(*a, **k)

        svc.core.submit, svc.core.dispatch = submit, dispatch
        loop = asyncio.get_running_loop()
        n = len(sched)
        latency = [None] * n
        outcome = ["unanswered"] * n
        late = []

        async def client(i, req, due):
            try:
                with TraceAnnotation("await"):
                    y = await svc.submit(req)
                if not y.is_ready():
                    await loop.run_in_executor(None, y.block_until_ready)
                latency[i] = time.perf_counter() - due
                outcome[i] = "ok"
                if i in sample:
                    self.kept[i] = (req.x, req.total_t, y)
            except (Rejected, Expired) as e:
                outcome[i] = f"refused:{type(e).__name__}"
            except Exception as e:  # noqa: BLE001 — a wrong answer, counted
                outcome[i] = f"error:{type(e).__name__}"

        await svc.start()
        tasks = []
        span = TraceAnnotation("window")
        span.__enter__()
        t0 = time.perf_counter() + 0.01
        for i, (due_s, d, steps, tenant, j) in enumerate(sched):
            due = t0 + due_s
            wait = due - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            with TraceAnnotation("generate"):
                late.append(time.perf_counter() - due)
                req = ServeRequest(self.spec, self.pool[d][j], steps,
                                   tenant=f"tenant{tenant}")
                tasks.append(asyncio.create_task(client(i, req, due)))
        grace = float(self.traffic["grace_s"])
        await asyncio.wait(tasks, timeout=max(1e-3, t0 + seconds + grace
                                              - time.perf_counter()))
        end = time.perf_counter()
        span.__exit__(None, None, None)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        await svc.stop()
        self.stats = svc.stats()
        self.bad_answers = sum(o.startswith(("error", "unanswered"))
                               for o in outcome)
        # a request that failed counts as waiting until the run gave up
        each = [latency[i] if outcome[i] == "ok" else end - (t0 + sched[i][0])
                for i in range(n)]
        lat = sorted(each)
        failed = sum(o != "ok" for o in outcome)
        late.sort()
        return {"attempted": n, "failed": failed, "start": t0,
                "request_p95_ms": 1e3 * pct(lat, 95),
                "info": {"requests": n, "failed": failed,
                         "request_p50_ms": 1e3 * pct(lat, 50),
                         "request_max_ms": 1e3 * lat[-1],
                         "first_half_p95_ms": 1e3 * pct(
                             sorted(each[:n // 2] or each), 95),
                         "second_half_p95_ms": 1e3 * pct(
                             sorted(each[n // 2:]), 95),
                         "late_p50_ms": 1e3 * pct(late, 50),
                         "late_max_ms": 1e3 * late[-1],
                         "outcomes": dict(collections.Counter(outcome))}}

    def counters(self) -> dict:
        return self.stats

    def release(self) -> None:
        pass

    def check(self) -> dict:
        taps = reference.taps_of(self.config)
        err = 0.0
        for x, steps, y in self.kept.values():
            e = float(reference.max_abs_err(y, reference.run(x, taps, steps)))
            err = e if math.isnan(e) else max(err, e)
            if math.isnan(err):
                break
        compared = len(self.kept)
        self.kept = {}
        return {"max_abs_err": err if compared else float("nan"),
                "bad_answers": self.bad_answers}


def pct(sorted_values: list, q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    return sorted_values[max(0, math.ceil(q / 100 * len(sorted_values)) - 1)]


DRIVERS = {"campaign": Campaign, "service": Service}


def driver_for(config: dict, traffic: dict, devices, **kw):
    kind = traffic.get("kind")
    if kind not in DRIVERS:
        raise ValueError(f"unknown traffic kind {kind!r}; "
                         f"expected one of {sorted(DRIVERS)}")
    return DRIVERS[kind](config, traffic, devices, **kw)
