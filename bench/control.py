"""Read the numbers ``correct`` compares over many seeds in one process: the
sound program's (the lower readings) or the control's (the upper ones).

    python3 bench/control.py --workload j2d5pt.campaign --seeds 1,2,3 --seconds 3
    python3 bench/control.py --workload j2d5pt.campaign --seeds 1,2,3 --seconds 3 --control

The control is the plain reference in the program's place, computed in
bfloat16 for a configuration that states float32
(``generator.LowPrecisionReference``): the program's own
``compute_dtype=bfloat16`` path does not lower on the chip (Mosaic has no
16-bit rotate).  Each seed runs the cell's own traffic
at its own size for a short window and prints one JSON line; the
benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import random
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from bench import generator, harness

    bench = harness.load_benchmark()
    cell = harness.cell_entry(bench, args.workload)
    config = harness.load_json(harness.config_path(cell))
    traffic = harness.load_json(harness.traffic_path(cell))
    limits = harness.load_json(harness.limits_path(cell))["numbers"]
    harness.use_compile_cache()
    devices = harness.chips(int(cell["chips"]))
    driver = generator.driver_for(config, traffic, devices,
                                  control=args.control)
    for seed in (int(s) for s in args.seeds.split(",")):
        driver.prepare(seed)
        win = driver.window(args.seconds, random.Random(seed))
        driver.release()
        ok, checks = harness.compare(driver.check(), limits)
        print(json.dumps({"workload": cell["name"], "control": args.control,
                          "seed": seed, "correct": ok,
                          "attempted": win["attempted"],
                          "failed": win["failed"], "checks": checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
