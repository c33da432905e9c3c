"""Record a small trace of two campaign calls on a TPU, for the trace tests.

    python3 bench/tests/record_trace.py bench/tests/data/j2d5pt_2calls_spans.xplane.pb

Sets up ``j2d5pt.campaign`` as the harness does (its configuration, its
traffic, two warm calls), then traces a window of two ``prog.run(y, 120)``
calls with the profiler options of a ``--trace 1`` run and copies the
``.xplane.pb`` to the path given.  Exits 2 without a TPU.
"""
from __future__ import annotations

import random
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(out: str) -> int:
    import jax

    from bench import clock, generator, harness, profile_reduce

    bench = harness.load_benchmark()
    cell = harness.cell_entry(bench, "j2d5pt.campaign")
    config = harness.load_json(harness.config_path(cell))
    traffic = harness.load_json(harness.traffic_path(cell))
    harness.use_compile_cache()
    try:
        devices = harness.chips(1)
    except harness.NoChip as e:
        print(f"record_trace: {e}", file=sys.stderr)
        return 2
    campaign = generator.Campaign(config, traffic, devices)
    campaign.prepare(7)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        # the first call ends before 1.2 call lengths, the second after
        info = campaign.window(1.2 * campaign.call_s, random.Random(7))
    finally:
        jax.profiler.stop_trace()
    shutil.copyfile(profile_reduce.find_xplane(trace_dir), out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"calls={info['attempted']} bytes={Path(out).stat().st_size}")
    print(clock.summary(clock.load(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
