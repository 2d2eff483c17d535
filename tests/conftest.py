"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

The program runs on the chip through chip_smoke.py and bench.py; unit and
sharding tests run on the host platform with 8 virtual devices so that
multi-chip code paths (shard_map over a Mesh) are tested without hardware.

The env var is overwritten (not setdefault) and the live config pinned
before any backend client is created, so a shell that names another
platform still gets the CPU here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

from celestia_app_tpu.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running integration tests"
    )
