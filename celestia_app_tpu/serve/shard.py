"""The sharded proof-serving plane: row-partitioned NMT forests.

$CELESTIA_SERVE_SHARDS=N (N > 1) partitions every retained height's two
flat (N_nodes, 90) forests row-wise across a 1D device mesh
(parallel/mesh.py, axis "serve"), under the SNIPPETS pjit contract:

  * ADMISSION lays the forest out exactly once — the forest build
    program itself carries committed `out_shardings`
    (kernels/fused.jit_forest_sharded), so there is no second
    device_put and no implicit reshard;
  * GATHER dispatches the whole micro-batch as ONE sharded program
    whose `in_shardings` name the same layout
    (parallel/mesh.sharded_gather_fn); each sample's proof-node rows
    are routed host-side to the shard that owns them (coordinate ->
    shard is a pure function of the level layout: contiguous equal row
    blocks, one integer divide) and no shard reads another's block.

Byte-identity is structural: a gather returns the same rows whatever
the layout, so the sharded path, the single-device batched path, and
the pure-host fallback are pinned identical (tests/test_serve_sharded).

Degradation ladder (read side, mirroring fused->staged->host):

  sharded gather        chaos seam proof.shard ($CELESTIA_CHAOS
      |  shard_fail=<p>) or any real fault in the sharded program
      v
  single-device batched the plain jnp.take the unsharded plane runs
      |  (ticks celestia_recoveries_total{seam="proof.shard"})
      v
  host                  the sampler's existing proof.serve fallback

The serve mesh shape and per-shard resident forest bytes surface on the
/healthz "serve" block (ForestCache.stats) and the
celestia_serve_shard_resident_bytes gauge; each sharded dispatch ticks
celestia_serve_shard_gathers_total{shard} with the rows each shard
served (bounded: one label value per shard).
"""

from __future__ import annotations

import os
import threading
import weakref

import numpy as np

from celestia_app_tpu.parallel.mesh import (
    SERVE_AXIS,
    device_mesh,
    padded_rows,
    route_to_shards,
    row_sharding,
    shard_of_row,
    sharded_gather_fn,
    sharded_share_gather_fn,
)
from celestia_app_tpu.serve.cache import FOREST_ROW, CachedForest


def serve_shards() -> int:
    """$CELESTIA_SERVE_SHARDS: how many devices the serve plane's
    forests are partitioned across (<=1 = the single-device plane,
    the default).  Clamped to the local device count, loudly; a
    MALFORMED value also warns loudly (once per value) instead of
    silently disabling sharding — the $CELESTIA_PIPE_PANEL precedent:
    an operator who asked for a sharded plane must not quietly get an
    unsharded one."""
    raw = os.environ.get("CELESTIA_SERVE_SHARDS", "0") or "0"
    try:
        want = int(raw)
    except ValueError:
        import warnings

        warnings.warn(
            f"CELESTIA_SERVE_SHARDS={raw!r} is not an integer; "
            "serving UNSHARDED",
            stacklevel=2,
        )
        return 0
    if want <= 1:
        return 0
    import jax

    have = len(jax.devices())
    if want > have:
        import warnings

        warnings.warn(
            f"CELESTIA_SERVE_SHARDS={want} but only {have} devices; "
            f"sharding the serve plane over {have}",
            stacklevel=2,
        )
        return have
    return want


def serve_mesh(shards: int):
    return device_mesh(shards, SERVE_AXIS)


def leaf_shard_of(k: int, shards: int, row: int, col: int,
                  axis: str = "row") -> int:
    """Owning shard of a sampled coordinate's level-0 forest node — THE
    coordinate->shard routing function (pure layout math, one divide),
    shared by the sampler's per-sample label (ShardedCachedForest
    .leaf_shard) and the serving planes' payload label
    (serve/api.payload_shard_label) so the two can never desynchronize.

    Row sampling proves leaf `col` of row tree `row`; column sampling
    the transpose.  The level-0 node of (tree, leaf) sits at flat row
    tree*width0 + leaf (forest_level_layout: offsets[0] == 0)."""
    n = 2 * k
    rows_per_shard = padded_rows(n * (2 * n - 1), shards) // shards
    tree, leaf = (col, row) if axis == "col" else (row, col)
    return shard_of_row(tree * n + leaf, rows_per_shard)


def eds_share_layout(buf):
    """(mesh, axis, shards) when `buf` is a device array row-partitioned
    across >1 devices on a named mesh axis — the committed layout the
    sharded extend pipeline (kernels/panel_sharded.py) retains its EDS
    under — else None.  Pure introspection: the serve plane discovers
    share sharding from the buffer it was handed, so the extend knob and
    the serve knob never have to agree."""
    try:
        from jax.sharding import NamedSharding
    except Exception:  # chaos-ok: no jax — host tier only
        return None
    sh = getattr(buf, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return None
    spec = tuple(sh.spec)
    if not spec or spec[0] is None or any(s is not None for s in spec[1:]):
        return None
    axis = spec[0]
    if isinstance(axis, (tuple, list)):
        if len(axis) != 1:
            return None
        axis = axis[0]
    shards = int(sh.mesh.shape[axis])
    if shards < 2:
        return None
    return sh.mesh, str(axis), shards


def sharded_share_gather(buf, coords) -> np.ndarray | None:
    """Gather [(row, col), ...] shares from a row-sharded EDS buffer as
    ONE sharded program, each coordinate routed host-side to its owning
    shard (flat share offset r*n + c; contiguous row blocks flatten to
    contiguous flat blocks, so it is the same one-divide routing the
    forest gather uses).  Returns None when `buf` is not share-sharded
    (the caller's single-device take answers); falls back the same way —
    ticking celestia_recoveries_total{seam="proof.shard"} — on an
    injected (chaos shard_fail) or real fault, so the read-side rung
    ladder covers shares exactly as it covers forests.  in_shardings
    name the extend pipeline's committed layout: a retained EDS is
    NEVER resharded by a serve read (pinned to buffer pointers in
    tests/test_panel_sharded.py)."""
    layout = eds_share_layout(buf)
    if layout is None:
        return None
    mesh, axis, shards = layout
    rows, n_cols, width = (int(x) for x in buf.shape)
    rows_local = rows // shards
    flat_idx = np.asarray(
        [r * n_cols + c for r, c in coords], dtype=np.int64
    )
    try:
        from celestia_app_tpu import chaos

        chaos.proof_shard()
        import jax

        local, (shard, slot), counts = route_to_shards(
            flat_idx, shards, rows_local * n_cols
        )
        fn = sharded_share_gather_fn(
            mesh, axis, rows_local, n_cols, width, int(local.shape[1])
        )
        idx = jax.device_put(local, row_sharding(mesh, axis))
        out = np.asarray(fn(buf, idx))  # (shards, bucket, width)
        _count_share_rows(counts)
        return out[shard, slot]
    except Exception:  # noqa: BLE001 — single-device rung answers
        from celestia_app_tpu.chaos.degrade import recoveries

        recoveries().inc(seam="proof.shard", outcome="degraded")
        return None


def _count_share_rows(counts) -> None:
    from celestia_app_tpu.trace.metrics import registry

    ctr = registry().counter(
        "celestia_serve_share_gathers_total",
        "EDS shares gathered per extend shard (one sharded program per "
        "share read; bounded: one label per shard)",
    )
    for s, n in enumerate(counts):
        if n:
            ctr.inc(int(n), shard=str(s))


class ShardedCachedForest(CachedForest):
    """One height's retained proof state, forests row-partitioned.

    Same surface as CachedForest — the sampler, the healing engine, and
    the spill tier are oblivious — plus the committed-sharding fields
    the never-reshards test pins: `committed_sharding` is the ONE
    NamedSharding both the admission build's out_shardings and every
    gather's in_shardings name.
    """

    def __init__(self, height: int, eds, row_flat, col_flat, mesh,
                 axis: str = SERVE_AXIS):
        super().__init__(height, eds, row_flat, col_flat)
        self.mesh = mesh
        self.axis = axis
        self.shards = mesh.shape[axis]
        n = 2 * self.k
        self.forest_rows = n * (2 * n - 1)
        self.rows_per_shard = padded_rows(self.forest_rows, self.shards) // self.shards
        self.committed_sharding = row_sharding(mesh, axis)

    # --- routing -------------------------------------------------------------
    def leaf_shard(self, row: int, col: int, axis: str = "row") -> int:
        """The bounded per-sample `shard` metric label (leaf_shard_of,
        instantiated on this entry's square size and shard count)."""
        return leaf_shard_of(self.k, self.shards, row, col, axis)

    # --- the sharded gather --------------------------------------------------
    def _sharded_gather(self, axis: str, flat_indices) -> np.ndarray:
        import jax

        flat = self._flat(axis)
        local, (shard, slot), counts = route_to_shards(
            flat_indices, self.shards, self.rows_per_shard
        )
        fn = sharded_gather_fn(
            self.mesh, self.axis, self.rows_per_shard,
            int(flat.shape[-1]), int(local.shape[1]),
        )
        idx = jax.device_put(local, self.committed_sharding)
        out = np.asarray(fn(flat, idx))  # (shards, bucket, 90)
        result = out[shard, slot]  # one fancy-index, batch order
        self._count_shard_rows(counts)
        return result

    @staticmethod
    def _count_shard_rows(counts) -> None:
        from celestia_app_tpu.trace.metrics import registry

        ctr = registry().counter(
            "celestia_serve_shard_gathers_total",
            "forest rows gathered per serve shard (one sharded program "
            "per micro-batch dispatch; bounded: one label per shard)",
        )
        for s, n in enumerate(counts):
            if n:
                ctr.inc(n, shard=str(s))

    def gather(self, axis: str, flat_indices) -> np.ndarray:
        """The read-side rung ladder: sharded program -> single-device
        take -> (caller's) host fallback.  A fault in the sharded
        dispatch — injected via the chaos seam proof.shard
        (shard_fail=<p>) or real — degrades THIS gather to the plain
        single-device path the unsharded plane runs, bit-identically;
        a fault there too propagates to the sampler, whose existing
        proof.serve fallback answers on the pure-host rung."""
        flat = self._flat(axis)
        if isinstance(flat, np.ndarray):  # spilled: host tier, base path
            return super().gather(axis, flat_indices)
        try:
            from celestia_app_tpu import chaos

            chaos.proof_shard()
            return self._sharded_gather(axis, flat_indices)
        except Exception:  # noqa: BLE001 — single-device rung answers
            from celestia_app_tpu.chaos.degrade import recoveries

            recoveries().inc(seam="proof.shard", outcome="degraded")
            return super().gather(axis, flat_indices)

    @property
    def gather_programs(self) -> int:
        return 2 if self.device_resident else 0

    def gather_proof(self, axis: str, flat_indices, coords
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Nodes through the sharded forest program, shares through
        their own take: the sharded plane's two dispatches."""
        return self.gather(axis, flat_indices), self.gather_shares(coords)

    # --- introspection -------------------------------------------------------
    def shard_resident_bytes(self) -> dict[str, int]:
        """Per-shard resident forest bytes (both axes) — the /healthz
        serve block's mesh view.  Uniform by construction (equal row
        blocks), reported per shard so a lopsided future layout shows."""
        per = self.rows_per_shard * 90 * 2
        return {str(s): per for s in range(self.shards)}


def build_entry(height: int, eds) -> CachedForest:
    """Build one height's retained entry: the admission seam shared by
    ForestCache.put / .readmit and the retention-disabled serve path.

    $CELESTIA_SERVE_SHARDS > 1 routes the forest build through the
    sharded program (committed out_shardings — laid out once, here) and
    wraps the entry as ShardedCachedForest; otherwise the single-device
    build, byte-identical, its forests widened once, here, to rows of
    serve/cache.FOREST_ROW bytes, so no gather ever relayouts them.
    """
    import jax.numpy as jnp

    shards = serve_shards()
    if shards > 1:
        from celestia_app_tpu.kernels.fused import jit_forest_sharded

        mesh = serve_mesh(shards)
        row_flat, col_flat = jit_forest_sharded(eds.k, mesh, SERVE_AXIS)(
            jnp.asarray(eds._eds)
        )
        return ShardedCachedForest(height, eds, row_flat, col_flat, mesh)
    from celestia_app_tpu.kernels.fused import jit_forest

    row_flat, col_flat = jit_forest(eds.k)(jnp.asarray(eds._eds))
    pad = ((0, 0), (0, FOREST_ROW - row_flat.shape[1]))
    return CachedForest(
        height, eds, jnp.pad(row_flat, pad), jnp.pad(col_flat, pad)
    )


# Per-cache contributions to the process-wide resident-bytes gauge:
# the gauge must be (a) re-set to 0 for a label whose bytes left the
# device tier (never report forests that no longer exist) and (b)
# AGGREGATED across caches in a multi-node process (one node's stats()
# refresh must not zero another node's resident bytes).  WeakKey so a
# dropped cache's contribution dies with it.
_CACHE_SHARD_BYTES: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()
_PUBLISHED_SHARD_LABELS: set[str] = set()
_GAUGE_LOCK = threading.Lock()


def mesh_stats(cache, entries) -> dict | None:
    """The /healthz serve block's "mesh" view over one cache's resident
    entries: shard count, axis, and per-shard resident forest bytes
    summed across heights; None when that cache's plane is unsharded.
    The exported gauge sums every live cache's contribution."""
    shards = 0
    per: dict[str, int] = {}
    for entry in entries:
        if not isinstance(entry, ShardedCachedForest):
            continue
        shards = max(shards, entry.shards)
        if entry.device_resident:
            for s, b in entry.shard_resident_bytes().items():
                per[s] = per.get(s, 0) + b
    with _GAUGE_LOCK:
        _CACHE_SHARD_BYTES[cache] = per
        totals: dict[str, int] = {}
        for contrib in _CACHE_SHARD_BYTES.values():
            for s, b in contrib.items():
                totals[s] = totals.get(s, 0) + b
        labels = set(totals) | _PUBLISHED_SHARD_LABELS
        if labels:
            from celestia_app_tpu.trace.metrics import registry

            gauge = registry().gauge(
                "celestia_serve_shard_resident_bytes",
                "resident forest bytes per serve shard (device tier, "
                "summed across this process's serve caches)",
            )
            # Every label ever published gets a fresh value — stale
            # shards (evicted, spilled, narrower mesh) drop to 0.
            for s in sorted(labels, key=int):
                gauge.set(totals.get(s, 0), shard=s)
            _PUBLISHED_SHARD_LABELS.update(labels)
    if not shards:
        return None
    return {
        "shards": shards,
        "axis": SERVE_AXIS,
        "per_shard_resident_bytes": per,
    }
