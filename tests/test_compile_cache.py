"""repro.utils.compile_cache: the env var wins, else a fixed in-checkout
directory.  Each case runs in a fresh interpreter, because the cache
directory is process-global jax configuration."""
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

_SCRIPT = """
import jax
from repro.utils.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def _run(env_dir):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, cwd=str(ROOT), timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_env_var_directory_is_kept(tmp_path):
    assert _run(tmp_path) == [str(tmp_path), str(tmp_path)]


def test_default_is_fixed_inside_the_checkout():
    want = str(ROOT / ".jax_cache")
    assert _run(None) == [want, want]
