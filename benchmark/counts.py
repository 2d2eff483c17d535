"""Work that the extend + DAH algorithm must do, from the square size alone.

Bytes: read the k x k ODS once, write the 2k x 2k EDS once, write the 4k
NMT roots (90 B each).  SHA-256 compressions: one leaf per EDS share
(0x00 || ns || share = 542 B, 9 blocks), 2k - 1 inner nodes per row and
per column tree (0x01 || 2 x 90 B = 181 B, 3 blocks), and the data root's
RFC 6962 tree over the 4k roots (91 B leaves and 65 B inner nodes, 2
blocks each).  The roofline share uses the bytes only: SHA-256 runs on the
vector units, for which the chip publishes no integer peak.
"""

SHARE = 512
NMT_ROOT = 90


def sha_blocks(message_bytes: int) -> int:
    return (message_bytes + 1 + 8 + 63) // 64


def extend_dah_bytes(k: int) -> int:
    return k * k * SHARE + 4 * k * k * SHARE + 4 * k * NMT_ROOT


def sha_compressions(k: int) -> int:
    n = 2 * k
    leaves = n * n * sha_blocks(1 + 29 + SHARE)
    inner = 2 * n * (n - 1) * sha_blocks(1 + 2 * NMT_ROOT)
    root = 2 * n * sha_blocks(1 + NMT_ROOT) + (2 * n - 1) * sha_blocks(1 + 64)
    return leaves + inner + root
