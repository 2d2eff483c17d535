"""Extended data square: the 2k x 2k erasure-coded share matrix.

Replaces rsmt2d.ExtendedDataSquare as consumed by the reference
(pkg/da/data_availability_header.go:65-75): construction fuses the RS
extension and all 4k NMT roots into one jitted device program per square
size; accessors mirror the rsmt2d surface (Row, Col, FlattenedODS, quadrant
namespace rules from pkg/wrapper/nmt_wrapper.go:93-114).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from celestia_app_tpu.constants import (
    MAX_CODEC_SQUARE_SIZE,
    NAMESPACE_SIZE,
    PARITY_NAMESPACE_BYTES,
    SHARE_SIZE,
)
from celestia_app_tpu.gf.rs import active_construction
from celestia_app_tpu.kernels.merkle import merkle_root_pow2
from celestia_app_tpu.kernels.nmt import leaf_digests, tree_roots_from_digests
from celestia_app_tpu.kernels.rs import extend_square_fn


def leaf_namespaces(eds: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-leaf namespaces for row trees and column trees.

    Q0 leaves carry the share's own namespace; every parity leaf (row >= k or
    col >= k) carries the parity namespace 0xFF^29.
    Returns (row_ns, col_ns): (2k, 2k, 29) each, row-tree-major and
    col-tree-major respectively.
    """
    n = eds.shape[0]
    share_ns = eds[..., :NAMESPACE_SIZE]  # (2k, 2k, 29)
    idx = jnp.arange(n)
    q0 = (idx[:, None] < k) & (idx[None, :] < k)  # (2k, 2k)
    parity = jnp.frombuffer(PARITY_NAMESPACE_BYTES, dtype=jnp.uint8)
    row_ns = jnp.where(q0[..., None], share_ns, parity)
    col_ns = row_ns.transpose(1, 0, 2)
    return row_ns, col_ns


def roots_fn(k: int):
    """The hashing half of the pipeline: eds (2k,2k,S) -> (row_roots,
    col_roots, droot).  Factored out so the bench decomposition can time
    NMT+DAH separately from the RS extension."""

    def roots(eds: jnp.ndarray):
        row_ns, _ = leaf_namespaces(eds, k)
        # The leaf digest at (i, j) is identical for the row-i tree and the
        # col-j tree (same namespace, same share), so hash the (2k, 2k) leaf
        # grid once and feed the column reduction its transpose.  Leaf hashes
        # are 9 SHA-256 blocks each vs 3 for inner nodes — this halves the
        # dominant hash cost.
        mins, maxs, hashes = leaf_digests(row_ns, eds)
        row_roots = tree_roots_from_digests(mins, maxs, hashes)  # (2k, 90)
        col_roots = tree_roots_from_digests(
            mins.transpose(1, 0, 2), maxs.transpose(1, 0, 2),
            hashes.transpose(1, 0, 2),
        )
        droot = merkle_root_pow2(jnp.concatenate([row_roots, col_roots], axis=0))
        return row_roots, col_roots, droot

    return roots


def _pipeline(k: int, construction: str):
    """Staged lowering: ods (k,k,512) -> (eds, row_roots (2k,90),
    col_roots (2k,90), droot (32,)) as extend-then-hash.  Kept as the
    bench A/B partner of kernels/fused.extend_and_dah_fn (bit-identical;
    the `parts` autotuner row measures both and seats the winner)."""
    extend = extend_square_fn(k, construction)
    roots = roots_fn(k)

    def run(ods: jnp.ndarray):
        eds = extend(ods)
        row_roots, col_roots, droot = roots(eds)
        return eds, row_roots, col_roots, droot

    return run


_STAGED_BUILT: set[tuple] = set()


@lru_cache(maxsize=None)
def _jit_pipeline(k: int, construction: str):
    _STAGED_BUILT.add((k, construction))
    from celestia_app_tpu.trace.device_ledger import track
    from celestia_app_tpu.trace.journal import note_jit_build

    note_jit_build("staged_pipeline")
    return track(
        jax.jit(_pipeline(k, construction)),
        "staged_pipeline", k=k, construction=construction, mode="staged",
    )


@lru_cache(maxsize=None)
def _host_pipeline(k: int, construction: str):
    """The degradation floor: the staged composition executed EAGERLY —
    no jitted program, every op its own dispatch.  Slow, but it removes
    compiled-program execution from the failure surface entirely, and it
    is bit-identical to both jitted lowerings (same ops, same order)."""
    fn = _pipeline(k, construction)

    def run(ods):
        return fn(jnp.asarray(ods))

    return run


def pipeline_cache_state(
    k: int, construction: str | None = None, *, owned: bool = False
) -> str:
    """"hit" when the jit wrapper the active seam would dispatch for
    (k, construction) is already built this process, else "miss" — the
    block journal's compile column, readable without building anything."""
    from celestia_app_tpu.kernels.fused import is_built, pipeline_mode_for_k

    construction = construction or active_construction()
    mode = pipeline_mode_for_k(k)
    if mode == "sharded_panel":
        from celestia_app_tpu.kernels.panel_sharded import is_sharded_warm

        return "hit" if is_sharded_warm(k, construction) else "miss"
    if mode == "panel":
        from celestia_app_tpu.kernels.panel import is_warm

        return "hit" if is_warm(k, construction) else "miss"
    if mode in ("fused", "fused_epi"):
        return "hit" if is_built(
            k, construction, donate=owned, epilogue=(mode == "fused_epi")
        ) else "miss"
    if mode == "host":
        return "hit"  # eager: nothing compiles, nothing can miss
    return "hit" if (k, construction) in _STAGED_BUILT else "miss"


def jit_pipeline(k: int, construction: str | None = None):
    """Cached single-dispatch pipeline, keyed on (k, RS construction) so an
    env-var flip mid-process never serves a stale-generator compile.
    Callers that must stay on one construction across several dispatches
    (repair's decode/verify pair, a live BlockPipeline) pass it explicitly.

    Routes through the fused/staged seam (kernels/fused.pipeline_mode —
    $CELESTIA_PIPE_FUSED): both lowerings are bit-identical, so the choice
    is a perf detail, never a correctness hazard.  This entry never
    donates its argument — callers that own their upload use
    jit_extend_and_dah(..., donate=True) directly (compute(), the block
    pipeline's feeder).

    Per-k: the panel-streamed lowering ($CELESTIA_PIPE_PANEL,
    kernels/panel.py) engages only for the square sizes its seam names,
    so the mode is resolved per square size (pipeline_mode_for_k)."""
    from celestia_app_tpu.kernels.fused import pipeline_mode_for_k

    construction = construction or active_construction()
    return _pipeline_for_mode(pipeline_mode_for_k(k), k, construction,
                              owned=False)


def _pipeline_for_mode(
    mode: str, k: int, construction: str | None = None, *, owned: bool = False
):
    """Resolve the pipeline callable for an EXPLICIT mode — the ladder-
    and retry-aware dispatch path (chaos/degrade.guarded_dispatch) re-
    resolves through here when the mode moves mid-retry."""
    from celestia_app_tpu.kernels.fused import jit_extend_and_dah

    construction = construction or active_construction()
    if mode == "sharded_panel":
        from celestia_app_tpu.kernels.panel_sharded import (
            sharded_panel_pipeline,
        )

        # Host-driven like the panel runner (input never donated), with
        # each step dispatched as ONE mesh-wide program; the EDS output
        # stays row-sharded under the committed extend-mesh layout.
        return sharded_panel_pipeline(k, construction)
    if mode == "panel":
        from celestia_app_tpu.kernels.panel import panel_pipeline

        # Host-driven loop of small jitted programs: the panel runner
        # never donates its input (only its internal accumulator), so
        # the owned/unowned distinction collapses here.
        return panel_pipeline(k, construction)
    if mode in ("fused", "fused_epi"):
        return jit_extend_and_dah(
            k, construction, donate=owned, epilogue=(mode == "fused_epi")
        )
    if mode == "host":
        return _host_pipeline(k, construction)
    return _jit_pipeline(k, construction)


def _owned_input_pipeline(k: int, construction: str | None = None):
    """The pipeline for a caller that OWNS its input buffer (a fresh
    upload): the donating fused program when the seam says fused, the
    staged jit otherwise.  compute() and warmup() both resolve through
    here so a server's warmed compile is exactly the one its blocks run."""
    from celestia_app_tpu.kernels.fused import pipeline_mode_for_k

    return _pipeline_for_mode(pipeline_mode_for_k(k), k, construction,
                              owned=True)


def _panel_fields(mode: str, k: int) -> dict:
    """Journal extras for a panel-streamed dispatch: how many panels the
    square streamed through (the per-dispatch panel-count instrument the
    giant-square memory model is judged by, next to the peak-bytes gauge
    journal.record refreshes).  Sharded dispatches additionally carry
    the mesh width (`shards`) and report their per-device step count."""
    if mode == "sharded_panel":
        from celestia_app_tpu.kernels.panel_sharded import (
            shards_for_k,
            sharded_panel_count,
        )

        return {"panels": sharded_panel_count(k), "shards": shards_for_k(k)}
    if mode != "panel":
        return {}
    from celestia_app_tpu.kernels.panel import panel_count

    return {"panels": panel_count(k)}


# --- batched (multi-square) pipeline ----------------------------------------


@lru_cache(maxsize=None)
def _jit_pipeline_batched(k: int, construction: str, batch: int):
    """vmap of the STAGED composition over a (batch, k, k, S) stack — the
    batched twin of _jit_pipeline, the ladder rung batched dispatch falls
    to when the fused family is degraded."""
    from celestia_app_tpu.trace.device_ledger import track
    from celestia_app_tpu.trace.journal import note_jit_build

    note_jit_build("staged_pipeline_batched")
    return track(
        jax.jit(jax.vmap(_pipeline(k, construction))),
        "staged_pipeline_batched",
        k=k, construction=construction, mode="staged", batch=batch,
    )


def _host_pipeline_batched(k: int, construction: str):
    """The batched degradation floor: each square through the eager host
    pipeline one by one (no compiled program at all), outputs stacked to
    the batched shape.  Exactly what "the unbatched rung" means at the
    bottom of the ladder."""
    run_one = _host_pipeline(k, construction)

    def run(odss):
        outs = [run_one(odss[b]) for b in range(odss.shape[0])]
        return tuple(
            jnp.stack([o[i] for o in outs]) for i in range(4)
        )

    return run


def _batched_pipeline_for_mode(
    mode: str, k: int, batch: int, construction: str | None = None,
    *, owned: bool = False,
):
    """The batched pipeline callable for an EXPLICIT mode: f(odss) with
    odss (batch, k, k, S) -> (eds, row_roots, col_roots, droots), each
    output carrying the leading batch axis.  Keyed per (k, batch, mode)
    through the underlying jit caches; fused_epi folds into the fused
    batched program (the epilogue tile schedule is per-square — see
    kernels/fused.py) so the ladder's batched modes are fused / staged /
    host."""
    from celestia_app_tpu.kernels.fused import jit_extend_and_dah_batched

    construction = construction or active_construction()
    if mode in ("fused", "fused_epi"):
        return jit_extend_and_dah_batched(
            k, batch, construction, donate=owned
        )
    if mode == "host":
        return _host_pipeline_batched(k, construction)
    return _jit_pipeline_batched(k, construction, batch)


def jit_pipeline_batched(k: int, batch: int, construction: str | None = None):
    """Cached batched pipeline for the ACTIVE mode — the multi-square
    analog of jit_pipeline.  Non-donating; the BlockPipeline dispatcher
    (which owns its uploads) resolves owned=True via
    _batched_pipeline_for_mode directly."""
    from celestia_app_tpu.kernels.fused import pipeline_mode

    return _batched_pipeline_for_mode(
        pipeline_mode(), k, batch, construction, owned=False
    )


def warmup_sizes(upto: int) -> list[int]:
    """The upto=N expansion: every power of two 1..upto (pure, so the
    contract is testable without paying the compiles)."""
    sizes = [1 << i for i in range(upto.bit_length())]
    return [k for k in sizes if k <= upto]


def warmup(
    square_sizes: list[int] | None = None,
    upto: int | None = None,
    constructions: tuple[str, ...] | None = None,
    batches: tuple[int, ...] = (),
) -> list[int]:
    """AOT-compile the fused pipeline for the given square sizes.

    Servers call this at startup so no block ever pays a compile on the
    critical path (SURVEY §7 hard part 4: recompilation must never sit on
    block production; reference TimeoutPropose is 10s). Pass either an
    explicit list or `upto` for every power of two 1..upto. Returns the
    warmed sizes.

    Only the given `constructions` (default: the active one) are warmed —
    flipping $CELESTIA_RS_CONSTRUCTION after warmup puts the next block's
    compile back on the critical path unless the flip target was listed.

    `batches` additionally warms the batched (vmap'd multi-square)
    programs at those coalesced sizes — a server running with
    $CELESTIA_PIPE_BATCH=B should warm batches=tuple(range(2, B+1)) so
    the dispatcher's first coalesced dispatch never pays a compile.

    Mode is resolved PER SIZE: a server configured with
    $CELESTIA_PIPE_PANEL warms the panel-streamed lowering's programs
    (row/column/roots pieces, incl. the short last panel) for exactly
    the sizes the seam engages at, and the materializing programs for
    the rest — the first giant block never eats the compile.
    """
    if square_sizes is None:
        assert upto is not None, "pass square_sizes or upto"
        square_sizes = warmup_sizes(upto)
    if constructions is None:
        constructions = (active_construction(),)
    import time

    from celestia_app_tpu.kernels.fused import pipeline_mode_for_k
    from celestia_app_tpu.trace import journal

    for construction in constructions:
        for k in square_sizes:
            ods = np.zeros((k, k, SHARE_SIZE), dtype=np.uint8)
            # Warm BOTH entries a server dispatches: the donating program
            # (compute(), the block pipeline's feeder) and the undonated
            # jit_pipeline (repair's re-extend, which re-reads its input
            # and must not donate).  Warming only one would leave the
            # other's first dispatch paying a compile on the block path.
            state = pipeline_cache_state(k, construction, owned=True)
            t0 = time.perf_counter()
            owned = _owned_input_pipeline(k, construction)
            jax.block_until_ready(owned(jnp.asarray(ods)))
            pipe = jit_pipeline(k, construction)
            if pipe is not owned:  # staged mode: both entries are one jit
                jax.block_until_ready(pipe(jnp.asarray(ods)))
            journal.record(
                "warmup", k, mode=pipeline_mode_for_k(k), compile=state,
                construction=construction,
                **_panel_fields(pipeline_mode_for_k(k), k),
                warm_ms=(time.perf_counter() - t0) * 1e3,
            )
            from celestia_app_tpu.trace.device_ledger import note_warmup

            note_warmup(k, construction, pipeline_mode_for_k(k))
            for batch in batches:
                if batch < 2:
                    continue  # batch-1 dispatch rides the unbatched entry
                if pipeline_mode_for_k(k) in ("panel", "sharded_panel"):
                    # Panel squares never coalesce (BlockPipeline forces
                    # batch=1 — a vmapped giant batch would materialize B
                    # full EDSes), so a batched program warmed here could
                    # never dispatch: skip the wasted compile.
                    break
                t0 = time.perf_counter()
                stack = jnp.asarray(
                    np.zeros((batch, k, k, SHARE_SIZE), dtype=np.uint8)
                )
                from celestia_app_tpu.kernels.fused import pipeline_mode

                jax.block_until_ready(
                    _batched_pipeline_for_mode(
                        pipeline_mode(), k, batch, construction, owned=True
                    )(stack)
                )
                journal.record(
                    "warmup", k, mode=pipeline_mode(), compile=state,
                    construction=construction, batch_size=batch,
                    warm_ms=(time.perf_counter() - t0) * 1e3,
                )
    return list(square_sizes)


def extra_warmup_sizes() -> list[int]:
    """$CELESTIA_WARMUP_K: comma/space-separated extra square sizes to
    AOT-warm at server startup, beyond the app's effective cap — the
    giant-square operator knob (a node serving k=1024 panel-streamed
    blocks must not compile on its first block).  Malformed or
    non-power-of-two entries are skipped loudly rather than failing the
    start; cmd/appd.py consumes this at --serve."""
    import os
    import sys

    raw = os.environ.get("CELESTIA_WARMUP_K", "")
    sizes: list[int] = []
    for tok in raw.replace(",", " ").split():
        try:
            k = int(tok)
        except ValueError:
            print(f"ignoring malformed CELESTIA_WARMUP_K entry {tok!r}",
                  file=sys.stderr)
            continue
        if 1 <= k <= MAX_CODEC_SQUARE_SIZE and k & (k - 1) == 0:
            sizes.append(k)
        else:
            print(f"ignoring out-of-range CELESTIA_WARMUP_K entry {k}",
                  file=sys.stderr)
    return sizes


# --- fused-vs-staged parity sentinel ---------------------------------------
#
# $CELESTIA_PARITY_SENTINEL=N re-runs every Nth computed block's DAH through
# the STAGED pipeline off the hot path (a daemon thread) and compares data
# roots, ticking celestia_parity_checks_total{result=match|mismatch|error}.
# A mismatch also writes a `parity_mismatch` trace row.  Nothing here ever
# raises into a serving plane, and the hot path only enqueues handles (the
# staged re-run and both host reads happen on the sentinel thread).

import threading as _sentinel_threading

_PARITY_LOCK = _sentinel_threading.Lock()
_PARITY_COUNT = 0
_PARITY_THREADS: list = []


def parity_sentinel_every() -> int:
    """$CELESTIA_PARITY_SENTINEL: check every Nth block (0 = disabled)."""
    import os

    try:
        return int(os.environ.get("CELESTIA_PARITY_SENTINEL", "0") or "0")
    except ValueError:
        return 0


def _maybe_parity_check(ods_host, k: int, construction: str, droot) -> None:
    """Hot-path side: count the block and, every Nth, hand the (immutable)
    ODS + fused root handles to a background checker."""
    every = parity_sentinel_every()
    if every <= 0:
        return
    from celestia_app_tpu.kernels.fused import pipeline_mode_for_k

    if pipeline_mode_for_k(k) not in ("sharded_panel", "panel", "fused",
                                      "fused_epi"):
        # Staged mode (and its eager host twin) already IS the reference
        # lowering: re-running it against itself would burn a duplicate
        # dispatch to report a meaningless "match".
        return
    global _PARITY_COUNT
    with _PARITY_LOCK:
        _PARITY_COUNT += 1
        if _PARITY_COUNT % every:
            return
        _PARITY_THREADS[:] = [t for t in _PARITY_THREADS if t.is_alive()]
    t = _sentinel_threading.Thread(
        target=_parity_check, args=(ods_host, k, construction, droot,
                                    _parity_provenance()),
        daemon=True, name="parity-sentinel",
    )
    with _PARITY_LOCK:
        _PARITY_THREADS.append(t)
    t.start()


def _parity_provenance() -> dict:
    """trace_id/height of the dispatch that armed this check, captured on
    the HOT-PATH side — the checker thread runs after the context is
    gone, and an unstamped mismatch row is unstitchable (trace_lint
    rule 9, trace/timeline.py)."""
    from celestia_app_tpu.trace.context import current_context

    ctx = current_context()
    return {
        "trace_id": ctx.trace_id if ctx is not None else None,
        "height": ctx.baggage.get("height") if ctx is not None else None,
    }


def _parity_check(ods_host, k: int, construction: str, droot,
                  provenance: dict | None = None) -> None:
    from celestia_app_tpu.trace.metrics import registry
    from celestia_app_tpu.trace.tracer import traced

    provenance = provenance or {"trace_id": None, "height": None}
    checks = registry().counter(
        "celestia_parity_checks_total",
        "fused-vs-staged DAH parity sentinel verdicts",
    )
    try:
        staged = _jit_pipeline(k, construction)(jnp.asarray(np.asarray(ods_host)))
        staged_root = np.asarray(staged[3]).tobytes()
        served_root = np.asarray(droot).tobytes()
        if staged_root == served_root:
            checks.inc(result="match")
            return
        checks.inc(result="mismatch")
        traced().write(
            "parity_mismatch", k=k, construction=construction,
            served=served_root.hex(), staged=staged_root.hex(),
            **provenance,
        )
        # A root divergence between bit-identical-by-contract lowerings
        # is the most forensically urgent trigger there is: capture the
        # full state before any ring buffer moves (never raises).
        from celestia_app_tpu.trace.flight_recorder import note_trigger

        note_trigger(
            "parity_mismatch", k=k, construction=construction,
            served=served_root.hex(), staged=staged_root.hex(),
        )
    except Exception as e:  # chaos-ok: the sentinel must never raise
        checks.inc(result="error")
        traced().write(
            "parity_mismatch", k=k, construction=construction,
            error=f"{type(e).__name__}: {e}"[:200], **provenance,
        )


def drain_parity_checks(timeout_s: float = 30.0) -> None:
    """Wait out in-flight sentinel checks (tests / orderly shutdown)."""
    with _PARITY_LOCK:
        threads = list(_PARITY_THREADS)
    for t in threads:
        t.join(timeout_s)


class ExtendedDataSquare:
    """Host handle to a device-computed EDS with its NMT roots."""

    def __init__(self, eds, row_roots, col_roots, data_root, k: int):
        self._eds = eds
        self._row_roots = row_roots
        self._col_roots = col_roots
        self._data_root = data_root
        self.k = k  # ODS width (original square size)
        # Proof-serving state: per-axis host NMT memo (one tree build per
        # touched row/col per HANDLE, not per request) and, when the serve
        # cache retained this height, the device-resident forest handle
        # (serve/cache.CachedForest) whose precomputed levels replace
        # host hashing entirely.
        self._tree_memo: dict = {}
        self._forest = None  # set by serve/cache.ForestCache.put
        # Retention listener: the continuous pipeline's buffer ring hooks
        # this (parallel/pipeline._BufferRing.pin via attach_forest) so a
        # serve-cache retention PINS the ring slot that fed this square —
        # a recycled donated buffer must never alias a retained EDS.
        self._retain_cb = None

    def attach_forest(self, forest) -> None:
        """Hook the retained device forest onto this handle so every
        proof path (incl. proof/share_proof's host constructors) stops
        re-hashing rows the device already hashed."""
        self._forest = forest
        self._tree_memo.clear()  # forest-backed trees are strictly better
        cb = self._retain_cb
        if cb is not None:
            cb()  # tell the feeding buffer ring this square is retained

    def leaf_namespace(self, row: int, col: int) -> bytes:
        """The namespace the (row, col) EDS leaf carries in its trees:
        the share's own namespace inside Q0, the parity namespace in
        every other quadrant (pkg/wrapper/nmt_wrapper.go:93-114)."""
        if row < self.k and col < self.k:
            return bytes(
                np.asarray(self._eds[row, col, :NAMESPACE_SIZE]).tobytes()
            )
        return PARITY_NAMESPACE_BYTES

    def _axis_tree(self, axis: str, index: int, *, host: bool = False):
        """Memoized per-line NMT for one row ("row") or column ("col").

        Returns an object with the `levels()` surface nmt.proof consumes:
        a forest-backed view (pure indexing) when the serve cache retained
        this square, else a freshly built host NamespacedMerkleTree whose
        leaves follow the full-EDS quadrant namespace rule (Q0 leaves own
        their namespace; EVERY other quadrant is parity — `_row_tree`'s
        old c<k rule was only valid for top rows).  `host=True` forces
        the from-scratch host build even with a forest resident — the
        sampler's bit-exactness fallback must not depend on the machinery
        it is the fallback FOR.
        """
        key = (axis, index, host)
        cached = self._tree_memo.get(key)
        if cached is not None:
            return cached
        if self._forest is not None and not host:
            tree = self._forest.line_tree(axis, index)
        else:
            from celestia_app_tpu.nmt.tree import NamespacedMerkleTree

            line = (
                np.asarray(self._eds[index])
                if axis == "row"
                else np.asarray(self._eds[:, index])
            )
            tree = NamespacedMerkleTree()
            for j in range(2 * self.k):
                r, c = (index, j) if axis == "row" else (j, index)
                ns = (
                    bytes(line[j, :NAMESPACE_SIZE].tobytes())
                    if r < self.k and c < self.k
                    else PARITY_NAMESPACE_BYTES
                )
                tree.push(ns + bytes(line[j].tobytes()))
        self._tree_memo[key] = tree
        return tree

    def row_tree(self, row: int, *, host: bool = False):
        return self._axis_tree("row", row, host=host)

    def col_tree(self, col: int, *, host: bool = False):
        return self._axis_tree("col", col, host=host)

    @property
    def width(self) -> int:
        """EDS width (2k), matching rsmt2d.ExtendedDataSquare.Width()."""
        return 2 * self.k

    @classmethod
    def compute(
        cls, ods: np.ndarray, construction: str | None = None
    ) -> "ExtendedDataSquare":
        from celestia_app_tpu.chaos.degrade import guarded_dispatch
        from celestia_app_tpu.trace import journal
        from celestia_app_tpu.trace.context import trace_span

        k = ods.shape[0]
        if k & (k - 1) or not 1 <= k <= MAX_CODEC_SQUARE_SIZE:
            raise ValueError(f"invalid square size {k}")
        assert ods.shape == (k, k, SHARE_SIZE), ods.shape
        sentinel_input = None  # a buffer still valid AFTER the dispatch
        if isinstance(ods, jax.Array):
            # jnp.asarray is a no-copy pass-through for a device array, so
            # donating here would invalidate the CALLER'S buffer.  Their
            # array, their lifetime: take the non-donating pipeline.
            if ods.dtype != jnp.uint8:  # the host path coerces; so must this
                ods = jnp.asarray(ods, dtype=jnp.uint8)
            state = pipeline_cache_state(k, construction)
            with trace_span("extend_dispatch", layer="device", k=k) as disp:
                mode, (eds, rr, cr, droot) = guarded_dispatch(
                    lambda m: _pipeline_for_mode(m, k, construction), ods, k=k
                )
            journal.record(
                "compute", k, mode=mode, compile=state,
                dispatch_ms=disp.get("duration_ms"),
                **_panel_fields(mode, k),
            )
            sentinel_input = ods  # undonated: still live and immutable
        else:
            # The upload below is this call's own buffer, never read again
            # — the donating pipeline may reuse it as extension scratch.
            # A retry after a REAL mid-dispatch failure re-uploads from
            # the host copy, so donation never poisons the retry.
            state = pipeline_cache_state(k, construction, owned=True)
            from celestia_app_tpu.kernels.fused import pipeline_mode_for_k

            panel = pipeline_mode_for_k(k) in ("panel", "sharded_panel")
            with trace_span("ods_upload", layer="device", k=k) as upload:
                # Panel mode streams panels out of the HOST copy one at a
                # time (the sharded runner additionally lays each step
                # out row-sharded across the mesh) — a whole-square
                # upload here would stage the giant ODS device-resident
                # next to the half-EDS accumulator, breaking the
                # documented residency bound.  A mid-call ladder fall
                # still works: the materializing jits accept the host
                # array and upload at dispatch.
                if panel:
                    x = np.ascontiguousarray(ods, dtype=np.uint8)
                else:
                    x = jnp.asarray(ods, dtype=jnp.uint8)
            with trace_span("extend_dispatch", layer="device", k=k) as disp:
                mode, (eds, rr, cr, droot) = guarded_dispatch(
                    lambda m: _pipeline_for_mode(
                        m, k, construction, owned=True
                    ),
                    x,
                    refresh=lambda: jnp.asarray(ods, dtype=jnp.uint8),
                    k=k,
                )
            journal.record(
                "compute", k, mode=mode, compile=state,
                upload_ms=upload.get("duration_ms"),
                dispatch_ms=disp.get("duration_ms"),
                **_panel_fields(mode, k),
            )
            sentinel_input = ods  # the host copy (x may be donated away)
        _maybe_parity_check(
            sentinel_input, k, construction or active_construction(), droot
        )
        return cls(eds, rr, cr, droot, k)

    # --- rsmt2d-surface accessors (host copies) ---------------------------
    def squared(self) -> np.ndarray:
        return np.asarray(self._eds)

    def row(self, i: int) -> np.ndarray:
        return np.asarray(self._eds[i])

    def col(self, j: int) -> np.ndarray:
        return np.asarray(self._eds[:, j])

    def flattened_ods(self) -> list[bytes]:
        q0 = np.asarray(self._eds[: self.k, : self.k])
        return [q0[i, j].tobytes() for i in range(self.k) for j in range(self.k)]

    def ods_namespaces(self) -> np.ndarray:
        """(k*k, NAMESPACE_SIZE) uint8 of the ODS share namespaces, row
        major — the namespace-range scan input (proof.ods_namespace_range);
        memoized so repeated namespace queries pay one device read."""
        cached = getattr(self, "_ods_ns", None)
        if cached is None:
            cached = self._ods_ns = np.asarray(
                self._eds[: self.k, : self.k, :NAMESPACE_SIZE]
            ).reshape(self.k * self.k, NAMESPACE_SIZE)
        return cached

    @staticmethod
    def _roots_list(roots) -> list[bytes]:
        """Roots as a list of bytes, WITHOUT a numpy S-dtype round trip:
        `np.asarray([...bytes...])` infers a fixed-width 'S' dtype whose
        scalars STRIP trailing 0x00 bytes, so any root ending in a zero
        byte (1 in 256) came back one byte short on handles constructed
        from Python lists — the swarm harness's per-leg handles served
        proofs that could never verify on exactly those lines."""
        if isinstance(roots, (list, tuple)):
            return [bytes(r) for r in roots]
        rr = np.asarray(roots)
        return [rr[i].tobytes() for i in range(rr.shape[0])]

    def row_roots(self) -> list[bytes]:
        return self._roots_list(self._row_roots)

    def col_roots(self) -> list[bytes]:
        return self._roots_list(self._col_roots)

    def data_root(self) -> bytes:
        if isinstance(self._data_root, (bytes, bytearray)):
            return bytes(self._data_root)  # no S-dtype trailing-NUL strip
        return np.asarray(self._data_root).tobytes()


def extend_shares(
    shares: list[bytes], construction: str | None = None
) -> ExtendedDataSquare:
    """Reference pkg/da/data_availability_header.go:65 ExtendShares parity.

    shares: row-major flattened ODS; length must be a square of a power of
    two within bounds.  `construction` pins the RS generator for callers
    that must hold one across several calls (a consensus loop mid-block);
    default resolves the active construction per call.

    $CELESTIA_SQUARE_BACKEND=bridge routes the extension through the C ABI
    worker (bridge/, the reference's wrapper/nmt_wrapper.go:73-86 seam for
    a host-language consensus daemon); any bridge fault falls back to the
    in-process device pipeline — the node must keep committing, and both
    paths are bit-identical, so the fallback never forks consensus.
    """
    from celestia_app_tpu.trace.context import trace_span

    n = len(shares)
    k = int(round(n ** 0.5))
    if k * k != n:
        raise ValueError(f"share count {n} is not a perfect square")
    if k & (k - 1) or k > MAX_CODEC_SQUARE_SIZE:
        raise ValueError(f"invalid square size {k}")
    with trace_span("share_pack", layer="host", k=k):
        for i, s in enumerate(shares):
            if len(s) != SHARE_SIZE:
                raise ValueError(
                    f"share {i} has length {len(s)}, want {SHARE_SIZE}"
                )
        ods = np.frombuffer(
            b"".join(shares), dtype=np.uint8
        ).reshape(k, k, SHARE_SIZE)
    if square_backend() == "bridge":
        result = _try_bridge_extend(ods)
        if result is not None:
            return result
    return ExtendedDataSquare.compute(ods, construction)


# --- bridge backend (C ABI worker) -----------------------------------------

import threading as _threading

_BRIDGE_CLIENT = None
_BRIDGE_LOCK = _threading.Lock()  # created at import: first-use is racy


def square_backend() -> str:
    """The active square-extension backend: "device" (in-process jit, the
    default) or "bridge" ($CELESTIA_SQUARE_BACKEND)."""
    import os

    return os.environ.get("CELESTIA_SQUARE_BACKEND", "device")


def _bridge_lib_path() -> str:
    import os

    default = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        "bridge", "build", "libcelestia_square_bridge.so",
    )
    return os.environ.get("CELESTIA_BRIDGE_LIB", default)


def _bridge_client():
    """Process-wide BridgeClient, created on first use (spawns the
    persistent worker). Raises on init failure — the caller falls back."""
    global _BRIDGE_CLIENT

    with _BRIDGE_LOCK:
        if _BRIDGE_CLIENT is None:
            from celestia_app_tpu.bridge.client import BridgeClient

            _BRIDGE_CLIENT = BridgeClient(_bridge_lib_path())
        return _BRIDGE_CLIENT


def _reset_bridge() -> None:
    """Drop the (possibly dead) client so a later block can retry init."""
    global _BRIDGE_CLIENT
    client, _BRIDGE_CLIENT = _BRIDGE_CLIENT, None
    if client is not None:
        try:
            client.shutdown()
        except Exception:  # chaos-ok: tearing down an already-dead worker
            pass


def _try_bridge_extend(ods: np.ndarray) -> ExtendedDataSquare | None:
    """One bridge round-trip; None on any fault (caller falls back).

    The fallback contract: a killed/hung worker must cost one failed call,
    not the block — the client is reset so the NEXT block retries a fresh
    worker while this one rides the device path.
    """
    import sys

    k = ods.shape[0]
    try:
        eds, rr, cr, droot = _bridge_client().extend_and_dah(ods)
        return ExtendedDataSquare(
            eds, rr, cr, np.frombuffer(droot, dtype=np.uint8), k
        )
    except Exception as e:  # chaos-ok: any bridge fault -> device path
        print(f"square bridge fault ({e}); falling back to device pipeline",
              file=sys.stderr)
        _reset_bridge()
        return None
