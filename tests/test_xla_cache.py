"""Where the persistent compilation cache lives (repro.xla_cache): the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else the
checkout's own ``.jax_cache`` -- an absolute path, whatever the cwd. Each
case runs in a fresh CPU process, since the cache is configured once, on
``import repro``."""
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
CHECKOUT = os.path.dirname(os.path.abspath(SRC))

PROBE = """
import json, jax, jax.numpy as jnp
import repro
from repro import xla_cache
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(json.dumps({"repro": xla_cache.cache_dir(),
                  "jax": jax.config.jax_compilation_cache_dir}))
"""


def _probe(cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "REPRO_XLA_CACHE")}
    env.update(PYTHONPATH=os.path.abspath(SRC), JAX_PLATFORMS="cpu",
               **env_over)
    out = subprocess.run([sys.executable, "-c", PROBE], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_var_dir_is_honoured_and_receives_entries(tmp_path):
    cache = tmp_path / "cc"
    got = _probe(str(tmp_path), JAX_COMPILATION_CACHE_DIR=str(cache))
    assert got == {"repro": str(cache), "jax": str(cache)}
    assert any(cache.iterdir()), "no compile-cache entry written"


def test_default_dir_is_checkout_path_whatever_the_cwd(tmp_path):
    want = os.path.join(CHECKOUT, ".jax_cache")
    for cwd in (str(tmp_path), CHECKOUT):
        got = _probe(cwd)
        assert got == {"repro": want, "jax": want}
    assert os.path.isabs(want)
    assert not (tmp_path / ".jax_cache").exists()
