"""Cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds, for
the tests that drive the harness without a chip."""
from __future__ import annotations

import time

from bench import harness

TINY_DOMAINS = {2: [64, 128], 3: [16, 16, 128]}
TINY_SHARDED = [96, 96, 128]


def cell(name: str):
    """``(bench, cell, config, traffic, limits)`` of ``name`` at a tiny
    domain, with its real limits."""
    bench = harness.load_benchmark()
    entry = harness.cell_entry(bench, name)
    config = harness.load_json(harness.config_path(entry))
    traffic = harness.load_json(harness.traffic_path(entry))
    limits = harness.load_json(harness.limits_path(entry))["numbers"]
    config["domain"] = TINY_DOMAINS[len(config["domain"])]
    traffic["steps"] = 12
    return bench, entry, config, traffic, limits


def service_cell():
    """j2d5pt under the service mix (``bench/traffic/service.json``) at
    tiny domains and rate, with the campaign's limit on the widest gap and
    no request left without its answer."""
    bench, entry, config, _, limits = cell("j2d5pt.campaign")
    traffic = harness.load_json(harness.BENCH / "traffic" / "service.json")
    traffic.update(domains=[[32, 128], [64, 128]], rate_per_s=20,
                   steps=[4, 8], pool_per_domain=2, check_per_group=2,
                   burst_size=4, burst_every_s=1)
    limits = dict(limits, bad_answers={"limit": 0})
    return bench, dict(entry, name="j2d5pt.service.tiny", traffic="service"), \
        config, traffic, limits


def sharded_cell():
    """The j3d7pt campaign over a 2x2 mesh at a tiny domain: the path a
    four-chip campaign cell runs (``run_sharded``), with j3d7pt's limits."""
    bench, entry, config, traffic, limits = cell("j3d7pt.campaign")
    config.update(domain=TINY_SHARDED, mesh=[2, 2])
    return bench, dict(entry, name="j3d7pt-2x2.tiny", chips=4), config, \
        traffic, limits


def run(name: str, devices, *, seed: int = 3, seconds: float = 0.3,
        driver_kw: dict | None = None) -> dict:
    """One run of the tiny ``name`` (a cell of ``BENCHMARK.json``, or
    ``"sharded"`` or ``"service"`` for :func:`sharded_cell` and
    :func:`service_cell`) on ``devices``, without the look for a chip; the
    result line as a dict."""
    special = {"sharded": sharded_cell, "service": service_cell}
    bench, entry, config, traffic, limits = (
        special[name]() if name in special else cell(name))
    return harness.run_cell(bench, entry, config, traffic, limits,
                            seed=seed, seconds=seconds, trace=False,
                            t_start=time.perf_counter(), devices=devices,
                            peaks=None, driver_kw=driver_kw)
