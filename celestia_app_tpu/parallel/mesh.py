"""Shared mesh / committed-sharding helpers for the sharded serve plane.

The write side (parallel/sharded_eds.py) built its own mesh + shard_map
plumbing inline; the read side needs the same two primitives, so they
live here for both:

  * a cached 1D device mesh over the first N local devices, on a
    dedicated axis name per consumer (the serve plane uses "serve" so a
    serve mesh never collides with the write pipeline's "data" axis);
  * the SNIPPETS pjit contract, applied to row-partitioned flat arrays:
    the producer commits `out_shardings` and every consumer commits the
    MATCHING `in_shardings`, so an array laid out once at admission is
    never resharded between retention and gather — resharding between
    two jitted programs is exactly the hidden cost the contract exists
    to forbid.

The unit of sharding here is a flat (R, W) byte matrix (an NMT forest:
R = every node of every tree, W = 90 digest bytes) partitioned row-wise:
shard i owns the contiguous row block [i*rps, (i+1)*rps) where
rps = padded_rows(R, n) // n.  `shard_of_row` is the pure host-side
routing function; `sharded_gather_fn` is the one program a whole
micro-batch's gathers dispatch as.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

SERVE_AXIS = "serve"
#: The extend plane's mesh axis (kernels/panel_sharded.py): the sharded
#: extend+DAH pipeline partitions row panels over it, and the retained
#: EDS keeps that layout all the way into the serve gather — a separate
#: name from "serve" so the share mesh and the forest mesh can coexist
#: (and differ in width) in one process.
EXTEND_AXIS = "extend"


@lru_cache(maxsize=None)
def device_mesh(n: int, axis: str = SERVE_AXIS):
    """1D mesh over the first n local devices on a named axis.

    Cached so every (n, axis) pair is ONE Mesh object — meshes key the
    jit caches below (and sharded_eds's), so identity matters.
    """
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if n < 1 or n > len(devs):
        raise ValueError(
            f"mesh wants {n} devices, {len(devs)} available"
        )
    return Mesh(np.array(devs[:n]), (axis,))


def row_sharding(mesh, axis: str = SERVE_AXIS):
    """NamedSharding partitioning axis 0 across the mesh — the ONE
    committed layout both the producer (forest build out_shardings) and
    the consumer (gather in_shardings) name, so the array never moves
    between them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(axis, None))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def padded_rows(rows: int, shards: int) -> int:
    """Smallest multiple of `shards` >= rows (row-wise partition needs
    equal blocks; the pad rows are gathered only as ignored fill)."""
    return ((rows + shards - 1) // shards) * shards


def shard_of_row(flat_row: int, rows_per_shard: int) -> int:
    """Owning shard of one flat row — the pure host-side routing
    function (contiguous equal blocks, so one integer divide)."""
    return flat_row // rows_per_shard


def bucket_pow2(n: int) -> int:
    """Next power of two >= n (>=1): per-shard gather slots are bucketed
    so the jit cache stays O(log max-batch), the da/repair discipline."""
    return 1 << max(0, (max(1, n) - 1).bit_length())


@lru_cache(maxsize=None)
def sharded_gather_fn(mesh, axis: str, rows_per_shard: int, width: int,
                      batch: int):
    """The batched sharded gather: ONE program per dispatch.

    f(flat (shards*rows_per_shard, width) row-sharded,
      idx  (shards, batch) int32 row-sharded, LOCAL row offsets)
        -> (shards, batch, width) row-sharded

    Each device takes only its own rows (indices are pre-routed
    host-side by shard_of_row), so no shard ever touches another's
    block and no collective moves forest bytes.  in_shardings are
    COMMITTED to the admission layout (row_sharding): a resident forest
    is never resharded by the gather — the SNIPPETS pjit contract.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.trace.journal import note_jit_build

    def local(flat_local, idx_local):
        # flat_local: (rows_per_shard, width); idx_local: (1, batch)
        return jnp.take(flat_local, idx_local[0], axis=0)[None]

    body = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=P(axis, None, None),
        check_vma=False,
    )
    fsh = row_sharding(mesh, axis)
    note_jit_build("serve_shard_gather")
    from celestia_app_tpu.trace.device_ledger import track

    return track(
        jax.jit(
            body,
            in_shardings=(fsh, fsh),
            out_shardings=row_sharding(mesh, axis),
        ),
        "serve_shard_gather",
        mode="sharded", batch=batch, shards=mesh.shape[axis],
    )


def row_sharding3(mesh, axis: str = SERVE_AXIS):
    """NamedSharding partitioning axis 0 of a RANK-3 array across the
    mesh — the committed layout of the sharded extend plane's share
    buffers ((rows, cols, SHARE_SIZE); the rank-2 row_sharding is the
    forests').  One producer commits it (the sharded panel pipeline's
    output programs), every consumer names it back (the serve plane's
    share gather), so the EDS never moves between extend, retention,
    and gather."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(axis, None, None))


def xor_allreduce(x, axis: str, n: int):
    """Bitwise-XOR all-reduce over a mesh axis: recursive doubling via
    lax.ppermute (log2 n exchanges, each the full working set).

    lax.psum adds integers — and a sum of packed GF(2) BYTES is not
    their XOR — so the mod-2 collective the sharded column phase needs
    is built from pairwise exchanges: at distance d every device XORs
    its partial with device (i ^ d)'s, and after log2(n) doublings every
    device holds the XOR of all n partials.  Exactness is the panel
    pipeline's own argument (mod-2 of a sum == XOR of per-part mod-2
    partials), applied across devices instead of across panels.
    Requires n to be a power of two (i ^ d must stay inside the mesh).
    """
    from jax import lax

    if n & (n - 1):
        raise ValueError(f"xor_allreduce needs a power-of-two axis, got {n}")
    d = 1
    while d < n:
        perm = [(i, i ^ d) for i in range(n)]
        x = x ^ lax.ppermute(x, axis, perm)
        d *= 2
    return x


@lru_cache(maxsize=None)
def sharded_share_gather_fn(mesh, axis: str, rows_local: int, n_cols: int,
                            width: int, batch: int):
    """The sharded EDS share gather: ONE program per dispatch.

    f(eds (shards*rows_local, n_cols, width) row-sharded,
      idx (shards, batch) int32 row-sharded, LOCAL FLAT share offsets)
        -> (shards, batch, width) row-sharded

    The share at (r, c) lives at flat offset r*n_cols + c of the
    row-major square; contiguous row blocks flatten to contiguous flat
    blocks, so shard-of-share is the same one-divide routing the forest
    gather uses (route_to_shards with rows_per_shard = rows_local *
    n_cols).  in_shardings name the extend pipeline's committed layout
    (row_sharding3): a retained EDS is never resharded by the serve
    plane's share reads — the PR 13 contract extended from the 90-byte
    forests to the shares themselves.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.trace.journal import note_jit_build

    def local(eds_local, idx_local):
        flat = eds_local.reshape(rows_local * n_cols, width)
        return jnp.take(flat, idx_local[0], axis=0)[None]

    body = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)),
        out_specs=P(axis, None, None),
        check_vma=False,
    )
    note_jit_build("serve_share_gather")
    from celestia_app_tpu.trace.device_ledger import track

    return track(
        jax.jit(
            body,
            in_shardings=(row_sharding3(mesh, axis), row_sharding(mesh, axis)),
            out_shardings=row_sharding3(mesh, axis),
        ),
        "serve_share_gather",
        mode="sharded", batch=batch, shards=mesh.shape[axis],
    )


def route_to_shards(flat_indices, shards: int, rows_per_shard: int):
    """Host-side routing of one micro-batch's flat gather rows —
    vectorized: this runs once per sharded dispatch on the serve hot
    path, so it is numpy arithmetic end to end, no per-index Python.

    Returns (local_idx (shards, bucket) int32, (shard, slot) index
    arrays locating each original row in the gathered output, counts
    per shard (the bounded per-shard metric)).  Pad slots point at
    local row 0 — valid rows gathered as ignored fill.
    """
    idx = np.asarray(flat_indices, dtype=np.int64)
    shard = idx // rows_per_shard
    counts = np.bincount(shard, minlength=shards) if idx.size else (
        np.zeros(shards, dtype=np.int64)
    )
    bucket = bucket_pow2(int(counts.max()) if idx.size else 1)
    # Slot of each row within its shard, in encounter order: positions
    # in the stable shard-sorted order, minus each shard's block start.
    order = np.argsort(shard, kind="stable")
    starts = np.cumsum(counts) - counts
    slot = np.empty(idx.size, dtype=np.int64)
    slot[order] = np.arange(idx.size) - np.repeat(starts, counts)
    local = np.zeros((shards, bucket), dtype=np.int32)
    local[shard, slot] = idx - shard * rows_per_shard
    return local, (shard, slot), counts
