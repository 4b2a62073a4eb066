"""utils.compile_cache: the cache directory is chosen from outside.

Each case runs in its own interpreter: the helper writes jax.config,
which must not leak into the suite's process."""

import os
import subprocess
import sys

from cleisthenes_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROBE = (
    "from cleisthenes_tpu.utils.compile_cache import enable_compile_cache;"
    "import jax;"
    "d = enable_compile_cache();"
    "assert d == jax.config.jax_compilation_cache_dir, d;"
    "print(d)"
)


def _probe(tmp_path, env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop(compile_cache.ENV_VAR, None)
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = env_dir
    # a different cwd each time: the default must not depend on it
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip()


def test_env_var_wins_and_helper_sets_nothing(tmp_path):
    outside = str(tmp_path / "outside_cache")
    assert _probe(tmp_path, outside) == outside


def test_default_is_fixed_inside_the_checkout(tmp_path):
    other = tmp_path / "elsewhere"
    other.mkdir()
    first, second = _probe(tmp_path, None), _probe(other, None)
    # two processes, two cwds, one path: nothing about the process or
    # the clock rides in it
    assert first == second == os.path.join(REPO, ".jax_cache")
