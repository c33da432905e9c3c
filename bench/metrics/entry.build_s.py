"""Host seconds the program spends building before it can run: the
seconds in ``compile_stencil`` (planning and construction, counter
``compile_s``) plus the seconds of each runner's first call, in which JAX
traces, lowers and compiles it or loads it from the compile cache
(``build_s``), read from ``repro.telemetry``.  The counters run from the
process's start; a campaign builds its one runner in set-up and the window
builds nothing (the harness reports the window's compiles, 0 in a sound
run), so the sum is the program's part of set-up.  Finds nothing where the
program keeps no such counters."""


def read(ctx):
    try:
        from repro import telemetry
    except ImportError:
        return None
    snap = telemetry.snapshot()
    return snap["compile_s"] + snap["build_s"]
