"""Every cell of ``BENCHMARK.json`` finds its files by name, and every name
and unit keeps to the benchmark's character rules."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import generator, harness

BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _names():
    out = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        out += [e["name"] for e in BENCH[key]]
    for c in BENCH["workloads"]:
        out += [c["config"], c["traffic"]]
    for c in BENCH["configs"]:
        out += c["reduced"]
    return out


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files(name):
    cell = harness.cell_entry(BENCH, name)
    config = harness.load_json(harness.config_path(cell))
    traffic = harness.load_json(harness.traffic_path(cell))
    limits = harness.load_json(harness.limits_path(cell))["numbers"]
    assert traffic["kind"] in generator.DRIVERS
    assert "max_abs_err" in limits
    generator.spec_for(config)           # the program's taps are the file's
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert harness.ROOT / entry["file"] == harness.config_path(cell)
    e2e = [m["name"] for m in harness.end_to_end_for(BENCH, name)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.per_layer_for(BENCH, name)
    assert layer
    for m in layer:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in e2e


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_name_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("unit", sorted({m["unit"] for k in (
    "end_to_end", "per_layer") for m in BENCH[k]}))
def test_unit_characters(unit):
    assert UNIT.match(unit), unit


def test_bounds_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS), m
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
