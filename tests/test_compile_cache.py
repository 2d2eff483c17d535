"""The persistent compile cache has one owner and two placements.

celestia_app_tpu/compile_cache.enable_compile_cache(): with
$JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it stands and
nothing is set; without it, the cache goes to a fixed path inside the
checkout, which git ignores.  Each case runs in a fresh process, since
JAX fixes the cache when it first compiles.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import sys
import celestia_app_tpu.compile_cache as cc
if len(sys.argv) > 1:
    cc.CACHE_DIR = sys.argv[1]
used = cc.enable_compile_cache()
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print("USED=" + used)
"""


def _probe(env_dir, default_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, str(default_dir)],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1].split("=", 1)[1]


@pytest.mark.parametrize("placed", [True, False], ids=["env", "default"])
def test_cache_lands_where_placed(tmp_path, placed):
    env_dir, default_dir = tmp_path / "from_env", tmp_path / "in_checkout"
    used = _probe(env_dir if placed else None, default_dir)
    want, other = (env_dir, default_dir) if placed else (default_dir, env_dir)
    assert used == str(want)
    assert want.is_dir() and any(want.iterdir()), "no cache entry written"
    assert not other.exists()


def test_default_dir_is_in_checkout_and_ignored():
    from celestia_app_tpu.compile_cache import CACHE_DIR

    assert os.path.dirname(CACHE_DIR) == REPO_ROOT
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f}
    assert os.path.basename(CACHE_DIR) in ignored
