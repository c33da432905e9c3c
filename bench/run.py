"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload j2d5pt.campaign --seed 7 --seconds 10 --trace 0

Finds the cell in ``BENCHMARK.json``, its configuration, traffic mix, limits
and per-layer metric readers by name under ``bench/``, sets up and warms up
(``setup_s``), measures for ``--seconds``, compares what the window produced
with the benchmark's plain reference, and prints one JSON object as the last
line of standard output, with the numbers compared and their limits as the
last lines of standard error.  ``--trace 1`` traces the window and reports
the per-layer metrics in place of the end-to-end ones.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)                  # import bench.*, never bench/*.py bare
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    bench = harness.load_benchmark()
    cell = harness.cell_entry(bench, args.workload)
    config = harness.load_json(harness.config_path(cell))
    traffic = harness.load_json(harness.traffic_path(cell))
    limits = harness.load_json(harness.limits_path(cell))["numbers"]
    harness.use_compile_cache()
    try:
        devices = harness.chips(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    peaks = harness.peaks_for(devices[0].device_kind)
    harness.log(f"[device] {devices[0].platform} {devices[0].device_kind} "
                f"using {len(devices)}")
    result = harness.run_cell(bench, cell, config, traffic, limits,
                              seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              devices=devices, peaks=peaks)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
