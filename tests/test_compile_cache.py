"""The persistent compilation cache lands where repro.compile_cache says:
in ``JAX_COMPILATION_CACHE_DIR`` when it is set, else in the fixed
``.jax_cache/`` under the checkout root.  Each case runs in a fresh CPU
subprocess, so the test process's own jax config stays untouched."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print("CACHE=" + enable_compile_cache(root=sys.argv[1]))
jax.jit(lambda x: x * 3 + 1)(jnp.arange(16.0)).block_until_ready()
"""


def _run(root, env_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(root)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("CACHE=")]
    return line[-1][len("CACHE="):]


def _entries(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


@pytest.mark.parametrize("with_env", [True, False])
def test_compile_cache_location(tmp_path, with_env):
    root = tmp_path / "checkout"
    root.mkdir()
    env_dir = tmp_path / "env_cache" if with_env else None
    used = _run(root, env_dir)
    fallback = root / ".jax_cache"
    if with_env:
        assert used == str(env_dir)
        assert any(e.endswith("-cache") for e in _entries(env_dir))
        assert not fallback.exists()
    else:
        assert used == str(fallback)
        assert any(e.endswith("-cache") for e in _entries(fallback))
