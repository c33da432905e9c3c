"""Sweep the offered rate of a service cell once, to find its knee: the
highest rate served with no rejection and no growing backlog.

    python3 bench/knee.py --config j2d5pt --traffic service --rates 100,200,400 --seconds 10

One process sets up once and runs the traffic mix at each rate in turn
(only ``rate_per_s`` changes), printing one JSON line per rate.  A backlog
grows where the second half of the window's requests waits much longer
than the first.  The traffic file then fixes its rate at four fifths of
the knee; the benchmark's runs never sweep.
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench import generator, harness

    config = harness.load_json(harness.BENCH / "configs"
                               / f"{args.config}.json")
    traffic = harness.load_json(harness.BENCH / "traffic"
                                / f"{args.traffic}.json")
    harness.use_compile_cache()
    devices = harness.chips(1)
    driver = generator.driver_for(config, traffic, devices)
    driver.prepare(args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        driver.traffic = dict(traffic, rate_per_s=rate)
        win = driver.window(args.seconds, random.Random(args.seed))
        driver.kept = {}
        print(json.dumps({"rate_per_s": rate,
                          "request_p95_ms": win["request_p95_ms"],
                          **win["info"], "counters": {
                              k: v for k, v in driver.counters().items()
                              if isinstance(v, int)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
