"""Pin the reference golden DAH vectors.

Reference: pkg/da/data_availability_header_test.go:29 (MinDAH), :45 (k=2),
:51 (k=128), :17-25 (nil/empty DAH hash = RFC-6962 empty hash). Shares are
built exactly as the reference's generateShares (:247-263): a v0 namespace
(version 0x00 + 18 zero prefix bytes + 10 bytes of 0x01) followed by 0xFF
fill to 512 bytes.

These three vectors pin the share format, NMT hasher, parity namespace
rules, and the row||col binary merkle — any regression in the device
pipeline breaks them.
"""

from __future__ import annotations

from celestia_app_tpu import merkle
from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE
from celestia_app_tpu.da.dah import (
    DataAvailabilityHeader,
    min_data_availability_header,
)
from celestia_app_tpu.da.eds import extend_shares

MIN_DAH_HASH = bytes.fromhex(
    "3d96b7d238e7e0456f6af8e7cdf0a67bd6cf9c2089ecb559c659dcaa1f880353"
)
K2_HASH = bytes.fromhex(
    "b56e4d251ac266f4b91cc5464b3fc7efcbdc888064647496d13133f0dc65ac25"
)
K128_HASH = bytes.fromhex(
    "0bd3abeeacfbb0b92dfbdac4a154868e3c4e79666f7fcf6c620bb90dd3a0dcf0"
)
EMPTY_SHA256 = bytes.fromhex(
    "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"
)


def _golden_share() -> bytes:
    ns = bytes([0x00]) + bytes(18) + bytes([0x01]) * 10
    assert len(ns) == NAMESPACE_SIZE
    return ns + b"\xff" * (SHARE_SIZE - NAMESPACE_SIZE)


def _golden_dah(k: int) -> DataAvailabilityHeader:
    shares = [_golden_share()] * (k * k)
    eds = extend_shares(shares)
    return DataAvailabilityHeader.from_eds(eds)


def test_min_dah_golden():
    dah = min_data_availability_header()
    assert dah.hash() == MIN_DAH_HASH
    dah.validate_basic()


def test_empty_dah_hash_is_rfc6962_empty():
    assert merkle.hash_from_byte_slices([]) == EMPTY_SHA256


def test_k2_dah_golden():
    dah = _golden_dah(2)
    assert len(dah.row_roots) == 4 and len(dah.column_roots) == 4
    assert dah.hash() == K2_HASH


# The reference's hard-cap square (a 256x256 EDS) through the default
# ExtendedDataSquare.compute path every block takes: k=2 pins the
# share/NMT/merkle vector chain, this leg pins the large-square program.
# ~20 s on a multi-core CPU, ~50 s on a 1-core one; kept in the fast tier.
def test_k128_dah_golden():
    dah = _golden_dah(128)
    assert len(dah.row_roots) == 256 and len(dah.column_roots) == 256
    assert dah.hash() == K128_HASH
