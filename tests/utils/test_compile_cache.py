"""`utils.enable_compile_cache`: one helper, placed from outside or at one
fixed path. JAX's cache directory is process-global, so each case runs in
a process of its own.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_SNIPPET = """
import os, sys
sys.path.insert(0, {repo!r})
import jax
from lodestar_tpu.utils import enable_compile_cache
got = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == got, jax.config.jax_compilation_cache_dir
# cache everything, however quick to compile, so one tiny program shows where entries go
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
def compile_cache_probe(x):
    return x * {salt} + 1
jax.jit(compile_cache_probe)(jax.numpy.arange(8)).block_until_ready()
print("CACHE_DIR=" + got)
"""


def _run(cwd, salt: int, cache_env: str | None) -> str:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    proc = subprocess.run(
        [sys.executable, "-c", _SNIPPET.format(repo=REPO, salt=salt)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr[-3000:]}"
    return proc.stdout.split("CACHE_DIR=")[1].strip()


def _entries(path: str) -> set[str]:
    """The probe program's cache entries (other processes may share the
    fixed directory)."""
    if not os.path.isdir(path):
        return set()
    return {name for name in os.listdir(path) if "compile_cache_probe" in name}


def test_variable_is_honoured_and_nothing_else_set(tmp_path):
    placed = tmp_path / "placed"
    fixed = os.path.join(REPO, ".jax_cache")
    before = _entries(fixed)
    got = _run(tmp_path, salt=os.getpid(), cache_env=str(placed))
    assert got == str(placed)
    assert _entries(str(placed)), "no cache entry where the variable points"
    assert _entries(fixed) == before, "entries also appeared under the checkout"


def test_fixed_path_from_any_working_directory(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir()
    b.mkdir()
    fixed = os.path.join(REPO, ".jax_cache")
    before = _entries(fixed)
    # the same program from two working directories: one path, and the
    # second run adds no entry because it found the first one's
    assert _run(a, salt=os.getpid() + 1, cache_env=None) == fixed
    after_first = _entries(fixed)
    assert after_first - before, "no cache entry under <checkout>/.jax_cache"
    assert _run(b, salt=os.getpid() + 1, cache_env=None) == fixed
    assert _entries(fixed) == after_first
    assert not os.listdir(a) and not os.listdir(b)
