"""Fused single-dispatch extend+DAH device pipeline.

One jitted program takes the k x k ODS as a single uint8 array and returns
the EDS, the 4k row/col NMT roots, and the final DAH data root with no
intermediate host transfer: RS row-extend -> RS col-extend -> share-to-leaf
namespace prefixing -> batched SHA-256 tree reduction, all inside one XLA
dispatch (reference hot path app/prepare_proposal.go:61-71 ->
pkg/da/data_availability_header.go:44-108).

Differences from the staged composition in da/eds.py's `_pipeline` (which
chains kernels/rs.extend_square_fn and da/eds.roots_fn):

  * `jit_extend_and_dah(..., donate=True)` donates the ODS argument, so
    XLA may reuse the caller's share buffer as scratch for the 4x
    extension instead of holding both live (the HBM high-water mark at
    k=512 drops by one 134 MB ODS);
  * a `roots_only` lowering drops the EDS from the outputs entirely —
    a DAH-only caller (block production needs just the roots once the
    shares are gossiped elsewhere) lets XLA free every share buffer
    before the tree reduction finishes;
  * one compile cache entry and one dispatch own the whole block path, so
    the autotuner can A/B it as a unit against the staged pair (whose
    extend/hash halves are also what the `parts` bench decomposes).
    The leaf schedule itself deliberately matches the staged path — all
    4k^2 leaves hash in ONE batched call; hashing the two square halves
    separately (to overlap with the column encode) was tried and measured
    slower on a serial schedule (smaller SHA batches, no real overlap).

Bit-identity with the staged path is pinned by tests/test_fused_pipeline.py
on the reference golden vectors; the bench autotuner (bench.py `parts` row)
measures `fused` against the seated staged RS + NMT pair and keeps
whichever wins.

Selection seam: $CELESTIA_PIPE_FUSED = "on" / "off" / "auto" (default:
fused).  da/eds.jit_pipeline routes through `pipeline_mode()`, so every
caller — ExtendedDataSquare, extend_block, BlockPipeline, repair's
re-extend — flips together and none can diverge.
"""

from __future__ import annotations

import os
import warnings
from functools import lru_cache

import jax
import jax.numpy as jnp

from celestia_app_tpu.constants import NAMESPACE_SIZE, PARITY_NAMESPACE_BYTES
from celestia_app_tpu.gf.rs import active_construction
from celestia_app_tpu.kernels.merkle import merkle_root_pow2
from celestia_app_tpu.kernels.nmt import leaf_digests, tree_roots_from_digests
from celestia_app_tpu.kernels.rs import encode_fn

@lru_cache(maxsize=None)
def _silence_unusable_donation_warning() -> None:
    """On backends without donation support (CPU), every donated dispatch
    warns and keeps the copy — expected, not actionable, so filter it the
    first time a donating program is built there.  Donation-capable
    backends keep the warning live: a donation that silently stops taking
    effect is a real perf regression someone should see."""
    if jax.default_backend() == "cpu":
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )


def pipeline_mode() -> str:
    """The active extend+DAH lowering: "fused" (default), "fused_epi",
    "staged", or "host" (all four bit-identical).

    $CELESTIA_PIPE_FUSED: "on" / "off" / "epi" / "auto" (default).  Auto
    is fused — the fused program is bit-identical to the staged pair
    (pinned on the golden vectors) and at worst matches it, so the staged
    path exists as a bench A/B candidate and an escape hatch, not a
    default.  "epi" selects the leaf-hash-epilogue variant (the column-
    phase extend feeds the bottom half's NMT leaf rounds from VMEM,
    kernels/rs_xor.extend_leaf_digests).  The bench autotuner flips this
    env for whichever candidate the parts row seats.

    The env choice is then floored by the degradation ladder
    (chaos/degrade.py): a process whose device dispatches keep failing is
    stepped fused_epi -> fused -> staged -> host by the circuit breaker,
    and because every caller routes through here, all of them move
    together.
    """
    from celestia_app_tpu.chaos.degrade import effective_device_mode

    return effective_device_mode(env_base_mode())


def env_base_mode() -> str:
    """The env-selected base lowering, WITHOUT the degradation ladder
    applied — the single parse of $CELESTIA_PIPE_FUSED (the ladder steps
    relative to this, so two copies of the branch must never diverge)."""
    val = os.environ.get("CELESTIA_PIPE_FUSED", "auto")
    if val == "off":
        return "staged"
    if val == "epi":
        return "fused_epi"
    return "fused"


def env_base_mode_for_k(k: int) -> str:
    """The env-selected base lowering for square size k: "sharded_panel"
    when the multi-chip extend partition engages at this k
    ($CELESTIA_EXTEND_SHARDS on top of the panel seam —
    kernels/panel_sharded.shards_for_k), "panel" when only the
    single-device panel-streaming seam engages ($CELESTIA_PIPE_PANEL —
    kernels/panel.panel_rows), else the k-less env_base_mode().  The
    degradation ladder steps relative to THIS, so a faulting sharded
    collective walks sharded_panel -> panel -> fused_epi/fused ->
    staged -> host."""
    from celestia_app_tpu.kernels.panel import panel_rows

    if not panel_rows(k):
        return env_base_mode()
    from celestia_app_tpu.kernels.panel_sharded import shards_for_k

    return "sharded_panel" if shards_for_k(k) else "panel"


def pipeline_mode_for_k(k: int) -> str:
    """The active extend+DAH lowering for square size k — pipeline_mode()
    with the per-k panel-streaming (and multi-chip panel-partition)
    seams applied above the fused rungs.  All six lowerings are
    bit-identical; the per-k selection is a memory/perf choice, never a
    correctness hazard."""
    from celestia_app_tpu.chaos.degrade import effective_device_mode

    return effective_device_mode(env_base_mode_for_k(k))


def extend_and_dah_fn(
    k: int,
    construction: str | None = None,
    roots_only: bool = False,
    epilogue: bool = False,
):
    """Build the fused program for square size k.

    Returns f(ods) where ods is (k, k, SHARE_SIZE) uint8:
      roots_only=False -> (eds, row_roots, col_roots, droot)
      roots_only=True  -> (row_roots, col_roots, droot)
    with eds (2k, 2k, S), roots (2k, 90), droot (32,).  The RS construction
    is resolved at build time; callers caching the result must key on it.

    epilogue=True is the LEAF-HASH-EPILOGUE variant (pipeline mode
    "fused_epi"): the column-phase extend feeds the bottom half's NMT
    leaf rounds directly from VMEM (kernels/rs_xor.extend_leaf_digests on
    TPU; the same ops staged through XLA elsewhere), so the bottom shares
    land in HBM once as output instead of round-tripping before hashing.
    It splits the leaf batch in two — the earlier experiment that split
    WITHOUT fusing into the extend measured slower, which is exactly why
    this variant is a tuned-seat candidate (bench parts row, >3%
    hysteresis) and not the default.  Bit-identical either way.
    """
    encode = encode_fn(k, construction)
    bottom_fn = None
    if epilogue:
        from celestia_app_tpu.kernels.rs_xor import bottom_leaf_fn

        bottom_fn = bottom_leaf_fn(k, construction, fallback_encode=encode)

    def run(ods: jnp.ndarray):
        parity = jnp.frombuffer(PARITY_NAMESPACE_BYTES, dtype=jnp.uint8)
        # Row phase: each of the k rows is a codeword batch along columns.
        q1 = encode(ods, 1)  # (k, k, S)
        top = jnp.concatenate([ods, q1], axis=1)  # (k, 2k, S)
        # Column phase contracts over the row axis directly — Q2/Q3 arrive
        # as the bottom rows with no transpose (row/col encodes commute).
        if epilogue:
            # Bottom shares + their (constant-namespace) leaf digests in
            # one program; only the top half still needs per-leaf
            # namespace bookkeeping (Q0 own ns, Q1 parity).
            bottom, bot_hashes = bottom_fn(top)  # (k,2k,S), (k,2k,32)
            eds = jnp.concatenate([top, bottom], axis=0)
            col = jnp.arange(2 * k)
            top_ns = jnp.where(
                (col < k)[None, :, None], top[..., :NAMESPACE_SIZE], parity
            )
            t_mins, t_maxs, t_hashes = leaf_digests(top_ns, top)
            par_ns = jnp.broadcast_to(parity, (k, 2 * k, NAMESPACE_SIZE))
            mins = jnp.concatenate([t_mins, par_ns], axis=0)
            maxs = jnp.concatenate([t_maxs, par_ns], axis=0)
            hashes = jnp.concatenate([t_hashes, bot_hashes], axis=0)
        else:
            bottom = encode(top, 0)  # (k, 2k, S)
            eds = jnp.concatenate([top, bottom], axis=0)  # (2k, 2k, S)

            # Q0 leaves carry the share's own namespace, every parity leaf
            # the parity namespace (pkg/wrapper/nmt_wrapper.go:93-114).
            # All 4k^2 leaves hash in ONE batched call — splitting by half
            # measured slower (smaller SHA batches, same serial schedule).
            idx = jnp.arange(2 * k)
            q0 = (idx[:, None] < k) & (idx[None, :] < k)
            row_ns = jnp.where(
                q0[..., None], eds[..., :NAMESPACE_SIZE], parity
            )

            # The digest at (i, j) serves both the row-i and col-j trees,
            # so each leaf is hashed exactly once and the column reduction
            # runs on the transpose (leaf hashing is 9 SHA-256 blocks vs 3
            # for nodes).
            mins, maxs, hashes = leaf_digests(row_ns, eds)
        row_roots = tree_roots_from_digests(mins, maxs, hashes)  # (2k, 90)
        col_roots = tree_roots_from_digests(
            mins.transpose(1, 0, 2),
            maxs.transpose(1, 0, 2),
            hashes.transpose(1, 0, 2),
        )
        droot = merkle_root_pow2(
            jnp.concatenate([row_roots, col_roots], axis=0)
        )
        if roots_only:
            return row_roots, col_roots, droot
        return eds, row_roots, col_roots, droot

    return run


# Keys whose jit wrapper has been built this process — the journal's
# compile hit/miss signal (a miss means the next dispatch traces and
# compiles; a hit reuses the cached executable).
_BUILT_KEYS: set[tuple] = set()


def is_built(
    k: int,
    construction: str | None = None,
    *,
    donate: bool = False,
    roots_only: bool = False,
    epilogue: bool = False,
) -> bool:
    key = (k, construction or active_construction(), donate, roots_only,
           epilogue)
    return key in _BUILT_KEYS


@lru_cache(maxsize=None)
def _jit_extend_and_dah(
    k: int, construction: str, donate: bool, roots_only: bool, epilogue: bool
):
    if donate:
        _silence_unusable_donation_warning()
    # Body runs on cache miss only: note the build for the journal's
    # hit/miss column and the celestia_jit_builds_total counter.
    _BUILT_KEYS.add((k, construction, donate, roots_only, epilogue))
    from celestia_app_tpu.trace.device_ledger import track
    from celestia_app_tpu.trace.journal import note_jit_build

    note_jit_build("extend_and_dah")
    return track(
        jax.jit(
            extend_and_dah_fn(k, construction, roots_only, epilogue=epilogue),
            donate_argnums=(0,) if donate else (),
        ),
        "extend_and_dah", k=k, construction=construction,
        mode="fused_epi" if epilogue else "fused",
    )


def jit_extend_and_dah(
    k: int,
    construction: str | None = None,
    *,
    donate: bool = False,
    roots_only: bool = False,
    epilogue: bool = False,
):
    """Cached jitted fused pipeline, keyed on (k, RS construction, donate,
    roots_only, epilogue).

    donate=True invalidates the caller's ODS device buffer — only pass it
    for a buffer the pipeline owns (a fresh `jnp.asarray` upload, a feeder
    thread's `device_put`), never a view of state the caller reads after
    the call (repair's survivor check re-reads its input, so it must not
    donate).  Backends without donation support (this image's CPU) ignore
    the hint and keep the copy — semantics are unchanged either way.
    """
    return _jit_extend_and_dah(
        k, construction or active_construction(), donate, roots_only,
        epilogue,
    )


# --- batched (vmap'd) multi-square dispatch ---------------------------------
#
# The cross-height continuous-batching leg (parallel/pipeline.py): when
# traffic produces many small same-k squares, B of them dispatch as ONE
# vmapped program over a (B, k, k, S) stack instead of paying B dispatch
# round-trips.  Its own compile-cache family, keyed per (k, construction,
# batch, donate, roots_only) — a batch of 4 k=128 squares is a different
# executable than 4 singles, and the journal's hit/miss column must say
# which one a dispatch paid for.
#
# Sharding contract (SNIPPETS.md pjit notes): the batched program takes no
# explicit in/out_shardings — outputs inherit the committed sharding of the
# batched input, so the (B, ...) layout one height's dispatch produces is
# exactly the layout the next height's dispatch consumes and batches never
# reshard between heights.  (On this image's single CPU device that is
# trivially true; on a mesh the batch axis stays wherever the uploader
# committed it.)
#
# The fused_epi seat deliberately folds into the plain fused body here: the
# leaf-hash epilogue is a per-square VMEM tile schedule (kernels/rs_xor),
# and vmapping a Pallas kernel is its own lowering project — all modes are
# bit-identical, so the batched program uses the one fused body and the
# ladder's epi/fused distinction stays an UNBATCHED perf detail.

_BATCHED_BUILT: set[tuple] = set()


def batched_is_built(
    k: int,
    batch: int,
    construction: str | None = None,
    *,
    donate: bool = False,
    roots_only: bool = False,
) -> bool:
    key = (k, construction or active_construction(), batch, donate,
           roots_only)
    return key in _BATCHED_BUILT


@lru_cache(maxsize=None)
def _jit_extend_and_dah_batched(
    k: int, construction: str, batch: int, donate: bool, roots_only: bool
):
    if donate:
        _silence_unusable_donation_warning()
    _BATCHED_BUILT.add((k, construction, batch, donate, roots_only))
    from celestia_app_tpu.trace.device_ledger import track
    from celestia_app_tpu.trace.journal import note_jit_build

    note_jit_build("extend_and_dah_batched")
    return track(
        jax.jit(
            jax.vmap(extend_and_dah_fn(k, construction, roots_only)),
            donate_argnums=(0,) if donate else (),
        ),
        "extend_and_dah_batched",
        k=k, construction=construction, mode="fused", batch=batch,
    )


def jit_extend_and_dah_batched(
    k: int,
    batch: int,
    construction: str | None = None,
    *,
    donate: bool = False,
    roots_only: bool = False,
):
    """Cached vmapped fused pipeline: f(odss) with odss (batch, k, k, S)
    uint8 -> (eds (batch,2k,2k,S), row_roots (batch,2k,90), col_roots,
    droots (batch,32)) — every square computed exactly as the unbatched
    fused program computes it (pinned bit-identical by
    tests/test_continuous_batching.py).  `batch` is part of the cache key:
    the dispatcher compiles one executable per coalesced size it actually
    sees."""
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    return _jit_extend_and_dah_batched(
        k, construction or active_construction(), batch, donate, roots_only
    )


# --- forest retention (the serve plane's read side) -------------------------
#
# The block-path program above materializes every NMT level on device and
# keeps only the 4k roots; the proof-serving plane (serve/) needs the WHOLE
# forest — every inner node of every row and column tree — so a batch of
# DAS sample requests is answered by gathers instead of host re-hashing.
#
# Deliberately a SEPARATE single-dispatch program over the retained EDS
# rather than a new output arm of extend_and_dah: widening the block-path
# program would add compile-cache keys and donation variants to every rung
# of the degradation ladder for a product only the read side consumes.
# Admission happens at commit, but the forest dispatch is an ASYNC jax
# enqueue — the leaf re-hash overlaps whatever runs next, and the commit
# path only pays the enqueue plus the (memoized) root reads.  The recompute
# is once per RETAINED height, bounded by $CELESTIA_SERVE_HEIGHTS.


def forest_level_layout(k: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(widths, offsets) of the flattened forest for 2k trees of 2k leaves.

    Level h holds 2k trees x (2k >> h) nodes; the flat (N, 90) array
    concatenates levels leaf-first, each level row-major by tree.  The
    node (tree t, level h, index i) lives at flat[offsets[h] + t*widths[h]
    + i] — the indexing contract serve/sampler.py's gather relies on.
    """
    n = 2 * k
    widths = []
    w = n
    while w >= 1:
        widths.append(w)
        w //= 2
    offsets, off = [], 0
    for w in widths:
        offsets.append(off)
        off += n * w
    return tuple(widths), tuple(offsets)


def forest_fn(k: int):
    """Build f(eds) -> (row_flat, col_flat): the complete namespaced-digest
    forests of both axes, flattened per forest_level_layout.

    Each node is the 90-byte min||max||hash digest (nmt/hasher.py wire
    form), so a proof node is a single flat-array row — byte-identical to
    what the host NamespacedMerkleTree computes for the same leaf
    (tests/test_das_proofs.py pins proof-level identity).
    """
    from celestia_app_tpu.kernels.nmt import (
        leaf_digests,
        tree_levels_from_digests,
    )

    def flatten(levels):
        return jnp.concatenate(
            [
                jnp.concatenate([m, x, h], axis=2).reshape(-1, 90)
                for m, x, h in levels
            ],
            axis=0,
        )

    def run(eds: jnp.ndarray):
        from celestia_app_tpu.da.eds import leaf_namespaces

        row_ns, _ = leaf_namespaces(eds, k)
        mins, maxs, hashes = leaf_digests(row_ns, eds)
        row_levels = tree_levels_from_digests(mins, maxs, hashes)
        col_levels = tree_levels_from_digests(
            mins.transpose(1, 0, 2),
            maxs.transpose(1, 0, 2),
            hashes.transpose(1, 0, 2),
        )
        return flatten(row_levels), flatten(col_levels)

    return run


@lru_cache(maxsize=None)
def jit_forest(k: int):
    """Cached jitted forest builder — ONE dispatch per retained height."""
    from celestia_app_tpu.trace.device_ledger import track
    from celestia_app_tpu.trace.journal import note_jit_build

    note_jit_build("forest")
    return track(jax.jit(forest_fn(k)), "forest", k=k)


@lru_cache(maxsize=None)
def jit_forest_sharded(k: int, mesh, axis: str):
    """Forest builder whose OUTPUT layout is the serve plane's committed
    row-wise shard partition (parallel/mesh.row_sharding).

    The flat (N, 90) forests are padded to a shard multiple inside the
    program and land already partitioned via committed `out_shardings`
    — the resident forest is laid out exactly once, at admission, and
    the gather program's matching `in_shardings`
    (parallel/mesh.sharded_gather_fn) means it is never resharded
    between retention and gather: the SNIPPETS pjit contract, applied
    to the read side the way parallel/sharded_eds.py applies it to the
    write side.
    """
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.parallel.mesh import padded_rows, row_sharding
    from celestia_app_tpu.trace.journal import note_jit_build

    shards = mesh.shape[axis]
    base = forest_fn(k)
    n = 2 * k
    rows = n * (2 * n - 1)  # sum of n*w over widths n, n/2, ..., 1
    per = padded_rows(rows, shards) // shards
    pad = per * shards - rows

    def local(eds: jnp.ndarray):
        # Every device hashes the whole (replicated) square and keeps its
        # own row block: GSPMD cannot partition the Pallas SHA kernel the
        # leaf batch selects on the chip, so the forest is built in a
        # per-device body and lands already in the committed layout.
        row_flat, col_flat = base(eds)
        if pad:
            row_flat = jnp.pad(row_flat, ((0, pad), (0, 0)))
            col_flat = jnp.pad(col_flat, ((0, pad), (0, 0)))
        start = jax.lax.axis_index(axis) * per
        return (
            jax.lax.dynamic_slice_in_dim(row_flat, start, per),
            jax.lax.dynamic_slice_in_dim(col_flat, start, per),
        )

    run = jax.shard_map(
        local, mesh=mesh, in_specs=P(), out_specs=(P(axis, None),) * 2,
        check_vma=False,
    )
    out_sh = row_sharding(mesh, axis)
    note_jit_build("forest_sharded")
    from celestia_app_tpu.trace.device_ledger import track

    return track(
        jax.jit(run, out_shardings=(out_sh, out_sh)),
        "forest_sharded", k=k, mode="sharded", shards=shards,
    )
