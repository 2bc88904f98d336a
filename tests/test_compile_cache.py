"""Where the entry points keep JAX's persistent compilation cache (in a
subprocess each: the cache directory is process-global JAX state)."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
used = enable_compile_cache()
print("USED", used)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("DEFAULT", DEFAULT_DIR)
if COMPILE:
    jax.jit(lambda a: jnp.sin(a) @ a.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", PROBE.replace("COMPILE", repr(compile_))],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return dict(line.split(" ", 1) for line in out.stdout.splitlines())


def test_cache_lands_where_the_environment_says(tmp_path):
    got = _probe(tmp_path, compile_=True)
    assert got["USED"] == str(tmp_path) == got["CONFIG"]
    assert os.listdir(tmp_path), "nothing was cached in the given dir"


def test_cache_defaults_to_the_checkout():
    got = _probe(None, compile_=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert got["USED"] == got["CONFIG"] == got["DEFAULT"] == want
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
