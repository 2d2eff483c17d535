"""Fused-leaf Pallas SHA-256: bit-identity with the concat+hash path.

The fused kernel assembles each NMT leaf message (0x00 || ns || share ||
SHA padding) in VMEM instead of materializing padded lane-major words in
HBM. The pallas kernel body is exactly `_leaf_tile_compute` — a pure jnp
function over the lane-major (leaves-on-lanes) byte layout — so off-TPU
these tests run that function directly (interpret mode cannot execute
the ~7k-op unrolled round structure in reasonable time); the pallas_call
wrapper itself is TPU-gated like the sibling test_sha_pallas.py, and
tests/test_chip_compile.py compiles it for v5e.
"""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE
from celestia_app_tpu.kernels.sha256 import (
    _leaf_tile_compute,
    _digest_bytes,
    sha256_leaves_pallas,
)


def _cases(n: int, seed: int = 3):
    rng = np.random.default_rng(seed)
    ns = rng.integers(0, 256, (n, NAMESPACE_SIZE), dtype=np.uint8)
    shares = rng.integers(0, 256, (n, SHARE_SIZE), dtype=np.uint8)
    return jnp.asarray(ns), jnp.asarray(shares)


def test_tile_compute_matches_hashlib():
    """The kernel body's digests equal hashlib over the exact leaf bytes
    (covers the in-kernel message assembly: prefix, ns, share windows at
    offsets 34/482, constant padding, BE packing, tile transpose)."""
    n = 8
    ns, shares = _cases(n)
    # eager: compiling the ~7k-op unrolled graph takes minutes on this
    # 1-core CPU; op-by-op execution is seconds
    out = _leaf_tile_compute(ns.T, (shares.T,), n)
    got = np.asarray(_digest_bytes(out.T))
    for i in range(n):
        msg = b"\x00" + bytes(np.asarray(ns[i])) + bytes(np.asarray(shares[i]))
        assert got[i].tobytes() == hashlib.sha256(msg).digest(), i


def test_tile_compute_two_byte_planes_constant_namespace():
    """The extend epilogue's call shape: a constant (parity) namespace as
    immediates and the share as two GF(2^16) byte planes (byte b of
    symbol s in plane b, row s)."""
    from celestia_app_tpu.constants import PARITY_NAMESPACE_BYTES

    n = 4
    _, shares = _cases(n, seed=7)
    planes = (shares[:, 0::2].T, shares[:, 1::2].T)
    out = _leaf_tile_compute(list(PARITY_NAMESPACE_BYTES), planes, n)
    got = np.asarray(_digest_bytes(out.T))
    for i in range(n):
        msg = b"\x00" + PARITY_NAMESPACE_BYTES + bytes(np.asarray(shares[i]))
        assert got[i].tobytes() == hashlib.sha256(msg).digest(), i


def test_tile_compute_matches_unfused_path():
    """Byte-identity with the production jnp path over a full tile."""
    from celestia_app_tpu.kernels.sha256 import _sha256_jnp

    n = 32
    ns, shares = _cases(n, seed=9)
    prefix = jnp.zeros((n, 1), dtype=jnp.uint8)
    msgs = jnp.concatenate([prefix, ns, shares], axis=1)
    want = np.asarray(_sha256_jnp(msgs))
    out = _leaf_tile_compute(ns.T, (shares.T,), n)  # eager, see above
    got = np.asarray(_digest_bytes(out.T))
    assert np.array_equal(got, want)


@pytest.mark.skipif(
    jax.devices()[0].platform != "tpu",
    reason="pallas_call wrapper needs a compiled Mosaic path (the body "
    "is covered above; digest equality re-asserted by bench on hardware)",
)
def test_pallas_call_wrapper_on_tpu():
    n = 2048 + 17  # crosses the lane tile: zero-pad + slice-back
    ns, shares = _cases(n, seed=5)
    from celestia_app_tpu.kernels.sha256 import _sha256_jnp

    prefix = jnp.zeros((n, 1), dtype=jnp.uint8)
    msgs = jnp.concatenate([prefix, ns, shares], axis=1)
    want = np.asarray(_sha256_jnp(msgs))
    got = np.asarray(sha256_leaves_pallas(ns, shares))
    assert np.array_equal(got, want)


def test_leaf_digests_rides_fused_kernel(monkeypatch):
    """CELESTIA_SHA_FUSED=on routes leaf_digests through the fused path
    with identical tree output (body-level off-TPU)."""
    from celestia_app_tpu.kernels import sha256 as sha_mod
    from celestia_app_tpu.kernels.nmt import leaf_digests

    t, l = 2, 4
    rng = np.random.default_rng(1)
    ns = jnp.asarray(
        rng.integers(0, 200, (t, l, NAMESPACE_SIZE), dtype=np.uint8))
    data = jnp.asarray(
        rng.integers(0, 256, (t, l, SHARE_SIZE), dtype=np.uint8))
    _, _, want = leaf_digests(ns, data)

    def body_path(ns2, shares2):
        out = _leaf_tile_compute(ns2.T, (shares2.T,), ns2.shape[0])
        return _digest_bytes(out.T)

    calls = []

    def tracked(ns2, shares2):
        calls.append(ns2.shape)
        return body_path(ns2, shares2)

    monkeypatch.setenv("CELESTIA_SHA_FUSED", "on")
    # the size gate keeps tiny batches on jnp; bypass it so the routing
    # itself is exercised at test scale
    monkeypatch.setattr(sha_mod, "_use_pallas_fused_leaves", lambda n: True)
    monkeypatch.setattr(sha_mod, "sha256_leaves_pallas", tracked)
    _, _, got = leaf_digests(ns, data)
    assert calls, "leaf_digests never routed through the fused path"
    assert np.array_equal(np.asarray(got), np.asarray(want))
