"""The entry points' persistent compile cache: the environment's directory
when set (and nothing else changed), else a fixed one in the checkout.

Each case runs in a child process pinned to the CPU: JAX settles on a
cache at its first compile, so a test worker cannot switch it back."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json
import os
import jax
import jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache

path = enable_compile_cache()
compiled = os.environ.get("COMPILE") == "1"
if compiled:
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
print(json.dumps({
    "path": path,
    "config_dir": jax.config.jax_compilation_cache_dir,
    "min_compile_s": jax.config.jax_persistent_cache_min_compile_time_secs,
    "entries": len(os.listdir(path)) if compiled else None,
}))
"""


def _child(env_dir=None, compile_=False, **extra_env) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src")
    env["COMPILE"] = "1" if compile_ else "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    env.update(extra_env)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_environment_directory_is_used_as_is(tmp_path):
    rep = _child(str(tmp_path))
    assert rep["path"] == rep["config_dir"] == str(tmp_path)
    assert rep["min_compile_s"] == 0.0         # every kernel is stored


def test_environment_directory_receives_the_entries(tmp_path):
    rep = _child(str(tmp_path), compile_=True)
    assert rep["path"] == str(tmp_path)
    assert rep["entries"] >= 1


def test_environment_minimum_compile_time_is_kept(tmp_path):
    rep = _child(str(tmp_path),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="2.5")
    assert rep["min_compile_s"] == 2.5


def test_default_directory_is_fixed_in_the_checkout():
    first, second = _child(), _child()
    assert first["path"] == second["path"] == str(ROOT / ".jax_cache")
    assert first["config_dir"] == first["path"]
    assert first["min_compile_s"] == 0.0       # every kernel is stored
