"""The launchers' persistent compilation cache (``launch/compile_cache``):
``JAX_COMPILATION_CACHE_DIR`` wins when set and nothing else is written;
otherwise the cache sits at one fixed path inside the checkout. Each case
compiles in a CPU-only child process so the suite's own JAX config stays
untouched."""

import os
import pathlib
import subprocess
import sys

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

_CHILD = '''
import sys
import jax
import jax.numpy as jnp
from repro.launch import compile_cache
if len(sys.argv) > 1:
    compile_cache.DEFAULT_DIR = sys.argv[1]
print(compile_cache.enable_compile_cache())
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)))
'''


def _run(env_dir, default_dir):
    env = dict(os.environ, PYTHONPATH=str(REPO / 'src'), JAX_PLATFORMS='cpu')
    env.pop(compile_cache.ENV_VAR, None)
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = str(env_dir)
    r = subprocess.run([sys.executable, '-c', _CHILD, str(default_dir)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[-1]


def test_env_var_dir_is_the_only_cache(tmp_path):
    env_dir, default_dir = tmp_path / 'from_env', tmp_path / 'default'
    assert _run(env_dir, default_dir) == str(env_dir)
    assert any(env_dir.iterdir())
    assert not default_dir.exists()


def test_default_dir_used_without_env_var(tmp_path):
    default_dir = tmp_path / 'default'
    assert _run(None, default_dir) == str(default_dir)
    assert any(default_dir.iterdir())


def test_default_dir_is_fixed_inside_the_checkout():
    assert compile_cache.DEFAULT_DIR == REPO / '.jax_cache'
