"""Pallas SHA-256 kernel equivalence (VERDICT r3 next-step #3).

The lane-parallel Pallas kernel must produce digests identical to the
fused-jnp path and to hashlib for the message geometries the NMT pipeline
uses (leaf 542 B, node 181 B, merkle 91/65 B).

TPU-only: Pallas has no compiled CPU path and interpreter mode takes
minutes per geometry (measured — a 2-block, 128-lane interpret run blows a
500 s budget), so on the CPU suite this file SKIPS and the dispatcher
(`sha256`) stays on the jnp path, which every NMT/DAH/golden test already
covers.  On TPU hardware (the bench/driver environment) these tests run
for real; scripts/verify_sha_pallas.py is the standalone drive.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from celestia_app_tpu.kernels.sha256 import _sha256_jnp, _sha256_pallas

pytestmark = pytest.mark.skipif(
    jax.devices()[0].platform != "tpu",
    reason="Pallas SHA-256 compiles only for TPU (interpret mode is minutes-slow)",
)

RNG = np.random.default_rng(19)


@pytest.mark.parametrize("length", [65, 91, 181, 542])
@pytest.mark.parametrize("n", [7, 1024, 1030])
def test_pallas_matches_jnp_and_hashlib(length, n):
    msgs = RNG.integers(0, 256, (n, length), dtype=np.uint8)
    want = np.asarray(_sha256_jnp(jnp.asarray(msgs)))
    got = np.asarray(_sha256_pallas(jnp.asarray(msgs)))
    assert np.array_equal(got, want)
    for i in (0, n - 1):
        assert bytes(want[i]) == hashlib.sha256(msgs[i].tobytes()).digest()
