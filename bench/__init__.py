"""Chip benchmark of the EBISU stencil system (see PERF.md and BENCHMARK.json)."""
