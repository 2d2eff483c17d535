"""Device-batched share commitments.

ProcessProposal's hot loop (3) (SURVEY §3.3: inclusion.CreateCommitment per
blob inside ValidateBlobTx, x/blob/types/blob_tx.go:98) recomputes every
blob's commitment every block on every validator.  Host hashing is
per-blob sequential; here ALL blobs' MMR chunks are hashed together: chunks
are grouped by size, each group is ONE batched NMT-forest call on the
device (kernels/nmt.tree_roots), and only the tiny merkle-over-peaks step
stays on host.  Chunk counts are padded to powers of two so the jit cache
stays bounded at (log sizes x log counts) entries.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from celestia_app_tpu.constants import NMT_NODE_SIZE, SHARE_SIZE, SUBTREE_ROOT_THRESHOLD
from celestia_app_tpu.inclusion.commitment import merkle_mountain_range_sizes
from celestia_app_tpu.merkle import hash_from_byte_slices
from celestia_app_tpu.shares.sparse import Blob, split_blob
from celestia_app_tpu.square.layout import round_up_power_of_two, subtree_width


@lru_cache(maxsize=None)
def _jit_tree_roots(n: int, leaves: int):
    from celestia_app_tpu.kernels.nmt import tree_roots

    return jax.jit(tree_roots)


# Commitment memo: a commitment is a pure function of (namespace, data,
# share_version, threshold), and the SAME blob is validated up to three
# times per inclusion (CheckTx admission, PrepareProposal filter,
# ProcessProposal validation — x/blob/types/blob_tx.go:98 runs each time).
# Keying on the blob's content hash collapses those to one device pass.
# Bounded FIFO so a flood of distinct blobs cannot grow it unboundedly.
_COMMIT_MEMO: dict[tuple, bytes] = {}
_COMMIT_MEMO_MAX = 2048
# The memo is shared across every node in the process (in-process
# clusters validate concurrently from relay/loader threads): all reads
# and evictions happen under this lock. Device hashing for misses runs
# OUTSIDE it — holding a lock across a jit dispatch would serialize the
# very work the batching exists to parallelize.
import threading as _threading

_COMMIT_MEMO_LOCK = _threading.Lock()


def _memo_key(blob: Blob, threshold: int) -> tuple:
    import hashlib

    return (
        blob.namespace.to_bytes(),
        hashlib.sha256(blob.data).digest(),
        blob.share_version,
        threshold,
    )


def create_commitments_batched(
    blobs: list[Blob], subtree_root_threshold: int = SUBTREE_ROOT_THRESHOLD,
    stats: dict | None = None,
) -> list[bytes]:
    """Commitments for many blobs with all hashing batched on device.

    Bit-identical to inclusion.create_commitment per blob (tested), just
    scheduled as one device call per distinct chunk size. Results are
    memoized by blob content, so revalidation of an already-seen blob
    (Prepare/Process after CheckTx) costs one sha256 of its data.
    `stats`, when given, receives `memo_hits`: the blobs answered from
    the memo.
    """
    if stats is not None:
        stats["memo_hits"] = 0
    if not blobs:
        return []

    keys = [_memo_key(b, subtree_root_threshold) for b in blobs]
    with _COMMIT_MEMO_LOCK:
        have = {k: _COMMIT_MEMO[k] for k in keys if k in _COMMIT_MEMO}
    missing = [i for i, k in enumerate(keys) if k not in have]
    if stats is not None:
        stats["memo_hits"] = len(keys) - len(missing)
    if not missing:
        return [have[k] for k in keys]
    fresh = _create_commitments_uncached(
        [blobs[i] for i in missing], subtree_root_threshold
    )
    with _COMMIT_MEMO_LOCK:
        for i, c in zip(missing, fresh):
            have[keys[i]] = c
            # FIFO-evict one per insert, so the memo can NEVER exceed its
            # bound: the old bulk pre-eviction emptied the whole dict when
            # len(missing) > _COMMIT_MEMO_MAX and then inserted past the
            # cap anyway (a single oversized batch left the memo holding
            # the entire flood).
            if keys[i] in _COMMIT_MEMO:
                continue
            while len(_COMMIT_MEMO) >= _COMMIT_MEMO_MAX:
                _COMMIT_MEMO.pop(next(iter(_COMMIT_MEMO)))
            _COMMIT_MEMO[keys[i]] = c
    return [have[k] for k in keys]


def _create_commitments_uncached(
    blobs: list[Blob], subtree_root_threshold: int = SUBTREE_ROOT_THRESHOLD
) -> list[bytes]:
    # Chunk every blob: (blob_idx, chunk_order, size, share_range).
    blob_shares: list[np.ndarray] = []
    blob_ns: list[bytes] = []
    chunks_by_size: dict[int, list[tuple[int, int, int]]] = {}
    chunk_counts: list[int] = []
    for bi, blob in enumerate(blobs):
        shares = split_blob(blob)
        arr = np.frombuffer(b"".join(s.raw for s in shares), dtype=np.uint8)
        blob_shares.append(arr.reshape(len(shares), SHARE_SIZE))
        blob_ns.append(blob.namespace.to_bytes())
        width = subtree_width(len(shares), subtree_root_threshold)
        sizes = merkle_mountain_range_sizes(len(shares), width)
        chunk_counts.append(len(sizes))
        cursor = 0
        for ci, size in enumerate(sizes):
            chunks_by_size.setdefault(size, []).append((bi, ci, cursor))
            cursor += size

    # One batched NMT-forest call per distinct chunk size.
    roots: dict[tuple[int, int], bytes] = {}
    for size, items in chunks_by_size.items():
        n = len(items)
        n_pad = round_up_power_of_two(n)
        data = np.zeros((n_pad, size, SHARE_SIZE), dtype=np.uint8)
        ns = np.zeros((n_pad, size, 29), dtype=np.uint8)
        for slot, (bi, _ci, start) in enumerate(items):
            data[slot] = blob_shares[bi][start : start + size]
            ns[slot] = np.frombuffer(blob_ns[bi], dtype=np.uint8)
        out = np.asarray(
            _jit_tree_roots(n_pad, size)(jnp.asarray(ns), jnp.asarray(data))
        )  # (n_pad, 90)
        for slot, (bi, ci, _start) in enumerate(items):
            roots[(bi, ci)] = out[slot].tobytes()
            assert len(roots[(bi, ci)]) == NMT_NODE_SIZE

    # Merkle over each blob's peaks (host; a handful of 90-byte leaves).
    return [
        hash_from_byte_slices([roots[(bi, ci)] for ci in range(chunk_counts[bi])])
        for bi in range(len(blobs))
    ]
