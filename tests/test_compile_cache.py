"""Where the entry points put JAX's persistent compilation cache. Each case
runs in a subprocess: the setting is process-wide and must not leak into
the other tests of this worker."""

import os
import subprocess
import sys

import pytest

from tests.helpers import ROOT

_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c",
                           _PROBE.format(compile=compile_)],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_dir(tmp_path, env_set):
    if env_set:
        returned, configured = _probe(tmp_path, True)
        assert returned == configured == str(tmp_path)
        assert any(tmp_path.iterdir()), "nothing was cached in the env dir"
    else:
        returned, configured = _probe(None, False)
        assert returned == configured == os.path.join(ROOT, ".jax_cache")
