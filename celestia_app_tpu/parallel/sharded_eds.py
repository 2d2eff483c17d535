"""Multi-chip EDS construction: shard_map over a 1D device mesh.

TPU-native mapping of the reference's per-axis parallelism (SURVEY §2.4):

  P2  row/column axis parallelism  -> the ODS is sharded row-wise across the
      mesh; each device RS-extends and NMT-hashes only its row block.
  P4  transpose between phases     -> one `all_to_all` over ICI re-shards the
      row-extended top half column-wise for the column encode.  This is the
      ring-attention / context-parallel analog for this workload
      (reference: implicit transpose inside rsmt2d, goroutines per axis;
      pkg/da/data_availability_header.go:74).

Row trees never move shares back: each device's column block is a
CONTIGUOUS, ALIGNED power-of-two slice of every row, so its leaf digests
reduce locally to ONE subtree node per row; a single `all_gather` of those
90-byte nodes (2k x 90 per device — vs 2k x 2k/n x 512 of shares) feeds the
top log2(n) levels, computed replicated.  Shares cross the interconnect
exactly once, in the column-phase reshard; everything after ships only
roots.  `make_sharded_dah_pipeline` drops the EDS output entirely for
DAH-only callers, so no share ever re-crosses the ICI (the second share
`all_to_all` in `make_sharded_pipeline` exists purely to hand the caller a
row-sharded EDS).

The final DAH merkle (pkg/da/data_availability_header.go:92-108) runs
INSIDE the shard_map, replicated, after one more 90-byte all_gather of
the column roots: at k >= 1024 its 4k-leaf batch selects the Pallas SHA
kernel, and a Mosaic kernel cannot be partitioned by GSPMD — every
hash in these programs runs in a per-device body.

All arithmetic is integer (uint8/int32 matmuls + SHA-256), so the sharded
pipeline is bit-identical to the single-chip path on every device count -
the determinism contract P1 of SURVEY §2.4.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from celestia_app_tpu.constants import (
    NAMESPACE_SIZE,
    PARITY_NAMESPACE_BYTES,
    SHARE_SIZE,
)
from celestia_app_tpu.gf.rs import active_construction
from celestia_app_tpu.kernels.merkle import merkle_root_pow2
from celestia_app_tpu.kernels.nmt import (
    leaf_digests,
    reduce_to_width,
    tree_roots_from_digests,
)


def _parity_ns() -> jnp.ndarray:
    return jnp.frombuffer(PARITY_NAMESPACE_BYTES, dtype=jnp.uint8)


def _local_extend_and_roots(k: int, n: int, axis: str, _encode):
    """The shared per-device body: row-sharded ODS block in ->
    (full_cols, row_roots, col_roots, droot).

    full_cols is this device's column block of the finished EDS
    ((2k/n, 2k, S), column-major); row_roots (2k, 90) are REPLICATED —
    finished from a 90-byte subtree all_gather, never a share reshard;
    col_roots (2k, 90) and the data root are replicated from one more
    90-byte all_gather.
    """

    def local_step(ods_local: jnp.ndarray):
        # ods_local: (k/n, k, S) — this device's row block of the ODS.
        parity = _parity_ns()
        i = lax.axis_index(axis)

        # Row phase: extend local rows. (k/n, k, S) -> (k/n, 2k, S)
        q1 = _encode(ods_local)
        top_local = jnp.concatenate([ods_local, q1], axis=1)
        # Materialize before the collective: XLA otherwise forwards the two
        # concat operands into a tuple all-to-all with mismatched layouts
        # (rejected by the HLO verifier on the CPU backend).
        top_local = lax.optimization_barrier(top_local)

        # P4: re-shard column-wise. Device j ends up with all k top rows of
        # its 2k/n-column block.  The ONLY collective that moves shares.
        cols_blk = lax.all_to_all(
            top_local, axis, split_axis=1, concat_axis=0, tiled=True
        )  # (k, 2k/n, S)
        cols_local = cols_blk.transpose(1, 0, 2)  # (2k/n, k, S)

        # Column phase: extend every local column of the top half, yielding
        # Q2 and Q3 at once (row/col encodes commute).
        bottom_cols = _encode(cols_local)  # (2k/n, k, S)
        full_cols = jnp.concatenate([cols_local, bottom_cols], axis=1)
        # full_cols: (2k/n, 2k, S) — column-sharded full EDS.

        # Column NMTs on the column-sharded layout (tree per local column,
        # leaves are the 2k rows). Parity namespace everywhere outside Q0
        # (pkg/wrapper/nmt_wrapper.go:93-114).
        local_cols = 2 * k // n
        gcol = i * local_cols + jnp.arange(local_cols)
        grow = jnp.arange(2 * k)
        col_q0 = (gcol[:, None] < k) & (grow[None, :] < k)
        col_ns = jnp.where(
            col_q0[..., None], full_cols[..., :NAMESPACE_SIZE], parity
        )
        # The leaf digest at grid position (row, col) is identical for the
        # row tree and the col tree, so hash each leaf exactly once.  Leaf
        # hashing is 9 SHA-256 blocks/leaf vs 3 for inner nodes; hashing on
        # the column-sharded layout halves the dominant cost per device.
        lmins, _, lhash = leaf_digests(col_ns, full_cols)
        col_roots_local = tree_roots_from_digests(lmins, lmins, lhash)

        # Row trees WITHOUT re-sharding shares: this device's 2k/n columns
        # are a contiguous, aligned power-of-two slice of every row tree's
        # leaves, so they reduce locally to one subtree node per row.  Only
        # those 90-byte nodes cross the ICI; the top log2(n) levels run
        # replicated on every device.
        rmins_l = lmins.transpose(1, 0, 2)  # (2k, 2k/n, 29): T=rows
        rhash_l = lhash.transpose(1, 0, 2)
        smin, smax, shash = reduce_to_width(rmins_l, rmins_l, rhash_l, 1)
        sub = jnp.concatenate(
            [smin[:, 0], smax[:, 0], shash[:, 0]], axis=1
        )  # (2k, 90) — this device's per-row subtree node
        gathered = lax.all_gather(sub, axis)  # (n, 2k, 90), replicated
        g = gathered.transpose(1, 0, 2)  # (2k, n, 90): L=device blocks
        gm = g[..., :NAMESPACE_SIZE]
        gx = g[..., NAMESPACE_SIZE : 2 * NAMESPACE_SIZE]
        gh = g[..., 2 * NAMESPACE_SIZE :]
        tm, tx, th = reduce_to_width(gm, gx, gh, 1)
        row_roots = jnp.concatenate(
            [tm[:, 0], tx[:, 0], th[:, 0]], axis=1
        )  # (2k, 90), replicated
        col_roots = lax.all_gather(col_roots_local, axis, tiled=True)
        droot = merkle_root_pow2(
            jnp.concatenate([row_roots, col_roots], axis=0)
        )  # (32,), replicated

        return full_cols, row_roots, col_roots, droot

    return local_step


def make_sharded_pipeline(
    k: int, mesh: Mesh, axis: str = "data", construction: str | None = None
):
    """Build the jitted multi-device pipeline for square size k.

    Returns f(ods) -> (eds, row_roots, col_roots, data_root) where ods is
    (k, k, SHARE_SIZE) uint8 sharded P(axis, None, None); eds comes back
    row-sharded, roots and data root replicated.

    Requires n | k (each device owns k/n ODS rows and 2k/n EDS rows/cols).
    """
    n = mesh.shape[axis]
    if k % n:
        raise ValueError(f"device count {n} must divide square size {k}")
    from celestia_app_tpu.kernels.rs import encode_fn
    from celestia_app_tpu.trace.journal import note_jit_build

    note_jit_build("sharded_pipeline")
    _encode = encode_fn(k, construction)
    body = _local_extend_and_roots(k, n, axis, _encode)

    def local_step(ods_local: jnp.ndarray):
        full_cols, row_roots, col_roots, droot = body(ods_local)
        # Hand the caller a ROW-sharded EDS: one more share all_to_all,
        # existing purely for the output layout (roots are already done).
        full_cols = lax.optimization_barrier(full_cols)
        rows_blk = lax.all_to_all(
            full_cols.transpose(1, 0, 2), axis, split_axis=0, concat_axis=1,
            tiled=True,
        )  # (2k/n, 2k, S) — this device's EDS row block.
        return rows_blk, row_roots, col_roots, droot

    pipeline = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=P(axis, None, None),
        out_specs=(P(axis, None, None), P(), P(), P()),
        check_vma=False,
    )

    in_sh = NamedSharding(mesh, P(axis, None, None))
    rep = NamedSharding(mesh, P())
    from celestia_app_tpu.trace.device_ledger import track

    return track(
        jax.jit(
            pipeline, in_shardings=in_sh, out_shardings=(in_sh, rep, rep, rep)
        ),
        "sharded_pipeline",
        k=k, construction=construction, mode="sharded", shards=n,
    )


def make_sharded_dah_pipeline(
    k: int, mesh: Mesh, axis: str = "data", construction: str | None = None
):
    """DAH-only multi-device pipeline: f(ods) -> (row_roots, col_roots,
    data_root), all replicated — no EDS output.

    Shares cross the ICI exactly once (the column-phase all_to_all);
    everything gathered afterwards is 90-byte roots.  This is the MULTICHIP
    bench row's lowering and the right entry for a DAH-only caller (block
    production where shares are gossiped from the builder, light-client
    header service); when the square itself is needed, use
    make_sharded_pipeline.  Bit-identical roots to the single-chip path.
    """
    n = mesh.shape[axis]
    if k % n:
        raise ValueError(f"device count {n} must divide square size {k}")
    from celestia_app_tpu.kernels.rs import encode_fn
    from celestia_app_tpu.trace.journal import note_jit_build

    note_jit_build("sharded_dah_pipeline")
    _encode = encode_fn(k, construction)
    body = _local_extend_and_roots(k, n, axis, _encode)

    def local_step(ods_local: jnp.ndarray):
        _full_cols, row_roots, col_roots, droot = body(ods_local)
        return row_roots, col_roots, droot

    pipeline = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=P(axis, None, None),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )

    in_sh = NamedSharding(mesh, P(axis, None, None))
    rep = NamedSharding(mesh, P())
    from celestia_app_tpu.trace.device_ledger import track

    return track(
        jax.jit(
            pipeline, in_shardings=in_sh, out_shardings=(rep, rep, rep)
        ),
        "sharded_dah_pipeline",
        k=k, construction=construction, mode="sharded", shards=n,
    )


@lru_cache(maxsize=None)
def default_mesh(n: int | None = None, axis: str = "data") -> Mesh:
    """1D mesh over the first n local devices (all of them by default)."""
    devs = jax.devices()
    n = len(devs) if n is None else n
    return Mesh(np.array(devs[:n]), (axis,))


def sharded_extend_and_dah(ods, mesh: Mesh, axis: str = "data"):
    """Host convenience: place a numpy ODS on the mesh and run the pipeline.

    Journals one block_journal row (source="sharded"): upload is the mesh
    placement, dispatch the async shard_map enqueue — no sync added."""
    import time

    from celestia_app_tpu.gf.rs import active_construction as _active
    from celestia_app_tpu.trace import journal

    k = ods.shape[0]
    state = "hit" if (k, mesh, axis, _active()) in _SHARDED_BUILT else "miss"
    fn = cached_pipeline(k, mesh, axis)
    sh = NamedSharding(mesh, P(axis, None, None))
    t0 = time.perf_counter()
    ods_dev = jax.device_put(jnp.asarray(ods, dtype=jnp.uint8), sh)
    t1 = time.perf_counter()
    out = fn(ods_dev)
    journal.record(
        "sharded", k, mode="sharded", compile=state,
        devices=mesh.shape[axis],
        upload_ms=(t1 - t0) * 1e3,
        dispatch_ms=(time.perf_counter() - t1) * 1e3,
    )
    return out


_SHARDED_BUILT: set[tuple] = set()


@lru_cache(maxsize=None)
def _cached_pipeline(k: int, mesh: Mesh, axis: str, construction: str):
    _SHARDED_BUILT.add((k, mesh, axis, construction))
    return make_sharded_pipeline(k, mesh, axis, construction)


def cached_pipeline(
    k: int, mesh: Mesh, axis: str = "data", construction: str | None = None
):
    """Cached sharded pipeline keyed on (k, mesh, axis, RS construction)."""
    return _cached_pipeline(k, mesh, axis, construction or active_construction())
