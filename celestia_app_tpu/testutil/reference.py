"""The plain host reference for the extend + NMT + DAH path.

numpy GF(2^m) Reed-Solomon (gf/rs.RSCodec.encode) and hashlib SHA-256
NMT trees (nmt/hasher.NmtHasher) — no JAX anywhere, so it is
independent of every device lowering it is compared with.  bench.py's
host baseline and chip_smoke.py's bit-for-bit checks both run it.
"""

from __future__ import annotations

import numpy as np

from celestia_app_tpu.constants import NAMESPACE_SIZE, PARITY_NAMESPACE_BYTES
from celestia_app_tpu.gf import codec_for_width
from celestia_app_tpu.merkle import hash_from_byte_slices
from celestia_app_tpu.nmt.hasher import NmtHasher


def extend_host(ods: np.ndarray) -> np.ndarray:
    """(k, k, S) ODS -> (2k, 2k, S) EDS: rows, then every column."""
    k = ods.shape[0]
    codec = codec_for_width(k)
    row_parity = np.stack([codec.encode(ods[i]) for i in range(k)])
    top = np.concatenate([ods, row_parity], axis=1)  # (k, 2k, S)
    col_parity = np.stack(
        [codec.encode(top[:, j]) for j in range(2 * k)], axis=1
    )
    return np.concatenate([top, col_parity], axis=0)


def line_root(line: np.ndarray, index: int, axis: str, k: int) -> bytes:
    """NMT root of EDS row or column `index` (line: (2k, S) shares): Q0
    leaves carry their own namespace, every other leaf the parity
    namespace (pkg/wrapper/nmt_wrapper.go:93-114)."""
    digests = []
    for j in range(2 * k):
        r, c = (index, j) if axis == "row" else (j, index)
        share = line[j].tobytes()
        ns = share[:NAMESPACE_SIZE] if r < k and c < k else PARITY_NAMESPACE_BYTES
        digests.append(NmtHasher.hash_leaf(ns + share))
    while len(digests) > 1:
        digests = [
            NmtHasher.hash_node(digests[t], digests[t + 1])
            for t in range(0, len(digests), 2)
        ]
    return digests[0]


def host_dah(ods: np.ndarray) -> tuple[list[bytes], list[bytes], bytes]:
    """(row_roots, col_roots, data_root) of an ODS, all on the host."""
    k = ods.shape[0]
    eds = extend_host(ods)
    rows = [line_root(eds[i], i, "row", k) for i in range(2 * k)]
    cols = [line_root(eds[:, j], j, "col", k) for j in range(2 * k)]
    return rows, cols, hash_from_byte_slices(rows + cols)
