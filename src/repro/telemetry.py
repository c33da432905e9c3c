"""The program's own instrumentation: host spans, device scopes, counters.

Every name starts with ``stencil.``, so a profiler trace of any process
that runs the program can be searched for it:

* :func:`span` — a host span (``jax.profiler.TraceAnnotation``) around an
  entry point or a runner build.  It records only while a profiler
  session is active; otherwise it costs about a microsecond.
* :func:`scope` — a device scope (``jax.named_scope``), applied while a
  runner is traced.  It lands in the ``op_name`` metadata of every HLO
  instruction the scoped operators become, and nowhere on the call path.
* the counters — process-wide seconds and counts the program adds to
  while it builds (``builds``, ``build_s``, ``compile_s``) and once per
  ``run_sharded`` call (``exchange_rounds``, ``halo_bytes``), read with
  :func:`snapshot` together with the program caches' own hit and miss
  counters.

An operator traces a run and reads the counters like this::

    with jax.profiler.trace("/tmp/trace"):
        y = prog.run(x, 120).block_until_ready()
    repro.telemetry.snapshot()["build_s"]

:func:`phases` reads the scopes back from a compiled runner's HLO text.
"""
from __future__ import annotations

import contextlib
import re
import threading
import time

import jax

PREFIX = "stencil."

_lock = threading.Lock()
_counters = {"builds": 0, "build_s": 0.0, "compile_s": 0.0,
             "exchange_rounds": 0, "halo_bytes": 0}

# `%pad.5 = f32[...] pad(...), ..., metadata={op_name="jit(run)/stencil.pad/
# scatter" ...}` -> ("pad.5", "jit(run)/stencil.pad/scatter")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.MULTILINE)
_SCOPE = re.compile(re.escape(PREFIX) + r"[\w\-]+")


def span(name: str, **args):
    """Host span ``stencil.<name>`` carrying ``args`` (shown in the trace
    as the event's stats)."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def scope(name: str):
    """Device scope ``stencil.<name>`` for the operators traced inside."""
    return jax.named_scope(PREFIX + name)


def add(name: str, value) -> None:
    """Add ``value`` to the counter ``name``."""
    with _lock:
        _counters[name] += value


@contextlib.contextmanager
def timed(name: str):
    """Add the host seconds the block takes to the counter ``name``."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        add(name, time.perf_counter() - t0)


def snapshot() -> dict:
    """The counters, and under ``caches`` the hit, miss and size counters
    of the program caches (``repro.api.program.cache_stats``)."""
    from repro.api.program import cache_stats

    with _lock:
        out = dict(_counters)
    out["caches"] = cache_stats()
    return out


def phases(hlo_text: str) -> dict[str, str]:
    """HLO instruction name (as a trace prints it, e.g. ``pad.5``) ->
    the outermost ``stencil.*`` scope in its ``op_name`` metadata, for
    every instruction of ``hlo_text`` that has one."""
    out = {}
    for name, op_name in _INSTRUCTION.findall(hlo_text):
        found = _SCOPE.search(op_name)
        if found:
            out[name] = found.group(0)
    return out
