"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a child process: the cache directory is process-wide
JAX configuration, and turning it on here would cache every later test's
compiles.
"""
import json
import os
import subprocess
import sys
import uuid

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Enable the cache as an entry point does, compile a function no earlier
# run has compiled (a fresh constant), and report where the entry went.
_SCRIPT = r"""
import json, os, sys
import jax
from repro.launch.compile_cache import enable_compile_cache

where = enable_compile_cache()
before = set(os.listdir(where)) if os.path.isdir(where) else set()
salt = float(sys.argv[1])
jax.jit(lambda x: x * salt + 1.0)(jax.numpy.ones(8)).block_until_ready()
print("RESULT " + json.dumps({
    "where": where,
    "config": jax.config.jax_compilation_cache_dir,
    "new_entries": len(set(os.listdir(where)) - before),
}))
"""


def _run(env_cache_dir=None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    # cache even a tiny compile (the defaults skip sub-second ones)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_cache_dir
    salt = str(uuid.uuid4().int % 10**9)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, salt],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode == 0, proc.stderr
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_env_var_directory_is_kept(tmp_path):
    cache = str(tmp_path / "jax-cache")
    got = _run(cache)
    assert got["where"] == cache and got["config"] == cache
    assert got["new_entries"] >= 1
    assert got["new_entries"] == len(os.listdir(cache))


def test_unset_env_var_uses_the_checkout_directory():
    got = _run()
    assert got["where"] == got["config"] == CHECKOUT_CACHE_DIR
    assert os.path.dirname(CHECKOUT_CACHE_DIR) == REPO
    assert got["new_entries"] >= 1
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert os.path.basename(CHECKOUT_CACHE_DIR) + "/" in f.read().split()
