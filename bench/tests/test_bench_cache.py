"""Set-up compiles into the persistent cache; a program that the window
compiles neither reads nor writes it, so every run pays it in full."""
import os
import subprocess
import sys

from bench import harness

SCRIPT = """
import jax, jax.numpy as jnp
from bench import harness
harness.enable_compile_cache()
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])))
harness.disable_compile_cache()
jax.jit(lambda x: x * 3 - 1)(jnp.ones(3)).block_until_ready()
print(len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])))
harness.enable_compile_cache()
jax.jit(lambda x: x * 5 - 2)(jnp.ones(3)).block_until_ready()
print(len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])))
"""


def test_window_compiles_bypass_the_persistent_cache(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", "import os\n" + SCRIPT],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    after_setup, after_window, after_reenable = map(
        int, p.stdout.split()[-3:])
    assert after_setup > 0
    assert after_window == after_setup
    assert after_reenable > after_window
