"""The entry points' persistent compilation cache: JAX_COMPILATION_CACHE_DIR
wins where it is set, else ``<checkout>/.jax_cache``.  Each case runs in a
fresh interpreter, since the cache directory is fixed once JAX has used
it."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

PROGRAM = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import use_checkout_cache
print(use_checkout_cache(sys.argv[1]))
jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.arange(64.0)).block_until_ready()
"""


def _run(checkout, env_dir=None):
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROGRAM, str(checkout)],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("env_set", [False, True], ids=["checkout", "env"])
def test_cache_lands_in_one_place(tmp_path, env_set):
    checkout, env_dir = tmp_path / "checkout", tmp_path / "env_cache"
    checkout.mkdir()
    used = _run(checkout, env_dir if env_set else None)
    want = env_dir if env_set else checkout / ".jax_cache"
    assert Path(used) == want.resolve() or Path(used) == want
    assert want.is_dir() and any(want.iterdir()), "nothing was cached"
    other = checkout / ".jax_cache" if env_set else env_dir
    assert not other.exists(), f"cache also written to {other}"
