"""The compile-cache helper the launchers call first thing.

Each case runs in a fresh interpreter, so no test of this process ever
gets a persistent cache from it.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import json, jax
from repro.launch.compile_cache import use_compile_cache
first, second = use_compile_cache(), use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
print(json.dumps([first, second, jax.config.jax_compilation_cache_dir]))
"""


def _probe(env_dir, compile_: bool):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c",
                        PROBE.format(compile=compile_)],
                       env=env, cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_honours_env_dir(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, the helper changes nothing
    and compiled programs land there."""
    first, second, active = _probe(tmp_path, compile_=True)
    assert first == second == active == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_cache_defaults_to_fixed_checkout_dir():
    """Unset, the cache is one fixed directory inside the checkout, the
    same on every call (nothing is compiled, so nothing is written)."""
    first, second, active = _probe(None, compile_=False)
    assert first == second == active == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
