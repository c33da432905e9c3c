"""The plain, unblocked reference's rate at a campaign cell's domain: one
reading beside the kernels' rates, not a metric of the benchmark.

    python3 bench/baseline.py --workload j2d5pt.campaign --seconds 5

``bench.reference.run`` (one shift-and-add pass over the field per step,
fused by XLA) advances the cell's field ``steps`` at a call in a closed
loop, as the campaign traffic does; it prints cell-updates per second.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    import jax

    from bench import generator, harness, reference

    bench = harness.load_benchmark()
    cell = harness.cell_entry(bench, args.workload)
    config = harness.load_json(harness.config_path(cell))
    steps = int(harness.load_json(harness.traffic_path(cell))["steps"])
    harness.use_compile_cache()
    devices = harness.chips(int(cell["chips"]))
    if config.get("mesh"):
        raise SystemExit("baseline: one-chip campaign cells only")
    taps = reference.taps_of(config)
    y = generator.make_field(1, config["domain"],
                             jax.sharding.SingleDeviceSharding(devices[0]))
    y = reference.run(y, taps, steps).block_until_ready()
    calls, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        y = reference.run(y, taps, steps).block_until_ready()
        calls += 1
    seconds = time.perf_counter() - t0
    rate = calls * steps * math.prod(config["domain"]) / seconds
    print(json.dumps({"workload": cell["name"], "steps": steps,
                      "calls": calls, "seconds": seconds,
                      "reference_gcells_per_s": rate / 1e9}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
