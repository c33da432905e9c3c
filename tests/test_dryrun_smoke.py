"""Dry-run machinery smoke test: lower+compile a reduced cell sweep in a
child process with 8 placeholder devices (the production run uses 512).

Keeps deliverable (e) guarded in CI without the full 98-cell sweep."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow

# Record schema contract: every "ok" cell must carry the full analysis
# payload (a silent per-cell exception produces "error" + traceback, and
# the jax cost_analysis()-returns-a-list regression surfaced as exactly
# such hidden error cells — hence this explicit schema gate).
OK_KEYS = {"arch", "shape", "mesh", "n_chips", "status", "compile_s",
           "memory", "cost_analysis_raw", "hlo", "terms", "dominant",
           "roofline_fraction", "useful_flops_ratio", "hbm_ok",
           "model_flops"}
MEMORY_KEYS = {"argument_bytes", "output_bytes", "temp_bytes",
               "alias_bytes", "code_bytes", "peak_per_device"}


def assert_ok_schema(rec):
    assert rec["status"] == "ok", rec.get("error", rec)
    missing = OK_KEYS - set(rec)
    assert not missing, f"ok record missing {missing}"
    assert MEMORY_KEYS <= set(rec["memory"])
    assert set(rec["terms"]) == {"compute_s", "memory_s", "collective_s"}
    assert set(rec["cost_analysis_raw"]) == {"flops", "bytes_accessed"}
    # normalized scalars
    assert isinstance(rec["cost_analysis_raw"]["flops"], (int, float))
    assert rec["hlo"]["dot_flops"] >= 0
    assert rec["memory"]["peak_per_device"] > 0
    assert rec["compile_s"] >= 0


def test_dryrun_cells_compile(tmp_path):
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "mamba2-130m,h2o-danube-1.8b",
         "--shape", "decode_32k,long_500k",
         "--mesh", "smoke", "--out", str(tmp_path)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    recs = [json.load(open(tmp_path / f)) for f in os.listdir(tmp_path)]
    assert len(recs) == 4
    for x in recs:
        assert_ok_schema(x)


def test_dryrun_stencil_cell(tmp_path):
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "stencil-suite", "--shape", "j3d7pt,j2d5pt",
         "--mesh", "smoke", "--out", str(tmp_path)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    recs = [json.load(open(tmp_path / f)) for f in os.listdir(tmp_path)]
    for x in recs:
        assert_ok_schema(x)
    # the deep-halo exchanges must appear in the collective stats
    assert any(x["hlo"]["coll_count"].get("collective-permute", 0) > 0
               for x in recs)


def test_dryrun_error_cells_are_loud(tmp_path):
    """A cell that raises must surface as status='error' with the
    exception and a traceback in the record — never silently 'ok'."""
    from repro.launch.dryrun import run_cell
    from repro.launch.mesh import make_host_mesh

    rec = run_cell("no-such-arch", "decode_32k", make_host_mesh(1, 1),
                   "smoke", str(tmp_path))
    assert rec["status"] == "error"
    assert "no-such-arch" in rec["error"] or "KeyError" in rec["error"]
    assert "Traceback" in rec["traceback"]
    saved = [json.load(open(tmp_path / f)) for f in os.listdir(tmp_path)]
    assert saved and saved[0]["status"] == "error"
