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


_SCOPED = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
enable_compile_cache(root=sys.argv[1])

def f(x):
    with jax.named_scope(sys.argv[2]):
        return x * 3 + 1

jax.jit(f)(jnp.arange(16.0)).block_until_ready()
"""


def _run_code(code, src, args, env_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(src)
    env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", code, *map(str, args)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]


def _programs(path, name):
    return [e for e in _entries(path)
            if e.startswith(name + "-") and e.endswith("-cache")]


def test_compile_cache_keys_on_op_names(tmp_path):
    """A program whose ops carry other scope names is compiled anew, not
    loaded with the names of the one cached first; the same names hit."""
    cache = tmp_path / "cache"
    src = os.path.join(_REPO, "src")
    _run_code(_SCOPED, src, [tmp_path, "edge_filter"], cache)
    first = _programs(cache, "jit_f")
    assert len(first) == 1
    _run_code(_SCOPED, src, [tmp_path, "edge_filter"], cache)
    assert _programs(cache, "jit_f") == first
    _run_code(_SCOPED, src, [tmp_path, "degree_count"], cache)
    assert len(_programs(cache, "jit_f")) == 2


_FROM_PACKAGE = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
from repro.core import engine
enable_compile_cache(root=sys.argv[1])
jax.jit(engine.removal_threshold, static_argnums=0)(0.5, jnp.float32(2.0))
"""


def test_compile_cache_shared_across_checkouts(tmp_path):
    """Two checkouts of the same code at different paths hit one entry:
    source paths enter the key relative to the checkout root."""
    import shutil

    cache = tmp_path / "cache"
    for name in ("a", "b"):
        shutil.copytree(os.path.join(_REPO, "src", "repro"),
                        tmp_path / name / "src" / "repro",
                        ignore=shutil.ignore_patterns("__pycache__"))
    _run_code(_FROM_PACKAGE, tmp_path / "a" / "src", [tmp_path / "a"], cache)
    first = _programs(cache, "jit_removal_threshold")
    assert len(first) == 1
    _run_code(_FROM_PACKAGE, tmp_path / "b" / "src", [tmp_path / "b"], cache)
    assert _programs(cache, "jit_removal_threshold") == first
