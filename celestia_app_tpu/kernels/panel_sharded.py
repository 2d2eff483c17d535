"""Multi-chip sharded extend+DAH: row panels partitioned over a device mesh.

kernels/panel.py streams a giant square's row panels through small jitted
programs — on ONE device.  This module turns that per-panel dispatch loop
into a per-device partition under the committed-shardings contract the
serve plane already runs (parallel/mesh.py, SNIPPETS pjit notes):

  * $CELESTIA_EXTEND_SHARDS=N ("auto" = every local device, floored to a
    power of two) gives each device one CONTIGUOUS slab of k/N ODS rows —
    a contiguous run of row panels — on the 1D "extend" mesh axis;
  * ROW PHASE — shard-local, no communication: each host-driven panel
    step is one shard_map program in which every device row-extends and
    leaf-hashes its own panel of the slab (the extend_leaf_digests
    epilogue shape, exactly kernels/panel._jit_row_panel batched over
    the mesh).  Panel heights are uniform across devices (every slab is
    the same k/N rows), so a panel height that does not divide the slab
    shortens the LAST step on every device at once — no padding, ever;
  * COLUMN PHASE, dense leg — one collective program per step: each
    shard computes its XOR partial products of the parity-row
    contraction (G_bits block-columns against its extended panel; mod-2
    of a sum is the XOR of per-shard mod-2 partials, the arXiv
    2108.02692 schedule split over the mesh) and a ppermute-butterfly
    XOR all-reduce (parallel/mesh.xor_allreduce — the psum-shaped
    collective for GF(2) bytes) combines them block-by-block into each
    device's OWN slice of the donated parity-row accumulator, so no
    device ever holds more than its half-EDS/N slice plus one panel;
  * COLUMN PHASE, FFT leg — the additive-FFT butterflies contract over
    the whole row axis and cannot XOR-split, but every column's chain is
    independent: one collective program all_to_alls the top half into
    2k/N-column blocks, runs kernels/fft.col_block_encode_fn shard-local
    over the column axis, and all_to_alls the bottom back row-sharded;
  * ROOTS — the digest grids all_gather (like the MULTICHIP subtree
    roots: GSPMD inserts the gather for the committed replicated
    out_shardings) and the final tree reduction is replicated;
  * OUTPUT — the EDS lands as ONE (2k, 2k, S) array under the committed
    row sharding (parallel/mesh.row_sharding3) and is retained AS-IS:
    ForestCache admission keeps the sharded buffers and the serve
    plane's share gathers route each coordinate to its owning shard
    (serve/shard.py via parallel/mesh.route_to_shards) — no reshard
    between extend, retention, and gather, pinned down to buffer
    pointers in tests/test_panel_sharded.py.

The sharded rung tops the degradation ladder (chaos/degrade.LADDER:
sharded_panel -> panel -> fused_epi -> fused -> staged -> host), and the
NEW chaos seam device.extend_shard ($CELESTIA_CHAOS extend_shard_fail=p)
fires mid-collective: a faulting sharded dispatch walks the process down
to the single-device panel runner with roots unchanged — every rung is
bit-identical (the module's whole output is pinned against the dense
full-square goldens for both RS constructions).

Per-device residency: one extended panel + the device's half-EDS/N
accumulator slice + its 61 B/leaf digest slabs — which is what raises
the practical codec ceiling toward k=4096 (MAX_CODEC_SQUARE_SIZE).
"""

from __future__ import annotations

import os
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from celestia_app_tpu.constants import (
    NAMESPACE_SIZE,
    PARITY_NAMESPACE_BYTES,
    SHARE_SIZE,
)
from celestia_app_tpu.gf.rs import active_construction, codec_for_width
from celestia_app_tpu.kernels.merkle import merkle_root_pow2
from celestia_app_tpu.kernels.nmt import leaf_digests, tree_roots_from_digests
from celestia_app_tpu.kernels.panel import (
    _resolved_config,
    panel_bounds,
    panel_rows,
)
from celestia_app_tpu.kernels.rs import encode_axis, encode_fn
from celestia_app_tpu.parallel.mesh import (
    EXTEND_AXIS,
    device_mesh,
    row_sharding,
    row_sharding3,
    xor_allreduce,
)


def _pow2_floor(n: int) -> int:
    return 1 << (n.bit_length() - 1) if n >= 1 else 0


_WARNED: set[str] = set()


def _warn_once(key: str, msg: str) -> None:
    if key in _WARNED:
        return
    _WARNED.add(key)
    import sys

    print(msg, file=sys.stderr)


def extend_shards() -> int:
    """$CELESTIA_EXTEND_SHARDS: how many devices the extend+DAH pipeline
    partitions row panels across (<=1 = the single-device panel runner).

    "auto" takes every local device, floored to a power of two (the XOR
    all-reduce butterfly and the equal-slab layout both need one).  An
    explicit integer is clamped to the device count and pow2-floored,
    LOUDLY — an operator who asked for a sharded extend must never
    silently get an unsharded one (the $CELESTIA_PIPE_PANEL precedent);
    a malformed value warns the same way.
    """
    raw = (os.environ.get("CELESTIA_EXTEND_SHARDS", "") or "").strip().lower()
    if raw in ("", "0", "off", "1"):
        return 0
    have = len(jax.devices())
    if raw == "auto":
        n = _pow2_floor(have)
        return n if n >= 2 else 0
    try:
        want = int(raw)
    except ValueError:
        _warn_once(
            f"malformed:{raw}",
            f"ignoring malformed CELESTIA_EXTEND_SHARDS value {raw!r} "
            "(want an integer shard count or 'auto'); extend sharding is "
            "OFF",
        )
        return 0
    if want <= 1:
        return 0
    n = min(want, have)
    n = _pow2_floor(n)
    if n != want:
        _warn_once(
            f"clamp:{want}:{n}",
            f"CELESTIA_EXTEND_SHARDS={want} clamped to {n} "
            f"({have} devices; power-of-two shard counts only)",
        )
    return n if n >= 2 else 0


def shards_for_k(k: int) -> int:
    """Shard count the sharded-panel seam engages with for square size k:
    0 when the panel seam is off for this k (sharding partitions the
    panel schedule, so there must be one), when $CELESTIA_EXTEND_SHARDS
    asks for <2 devices, or when k is smaller than the mesh (a k=2
    square over 8 devices has no rows to give most of them).  Both k and
    the shard count are powers of two, so engagement implies equal
    slabs."""
    if not panel_rows(k):
        return 0
    n = extend_shards()
    if n < 2 or k < n:
        return 0
    return n


def extend_mesh(shards: int):
    return device_mesh(shards, EXTEND_AXIS)


def local_panel_bounds(k: int, shards: int) -> tuple[tuple[int, int], ...]:
    """The per-device panel schedule: each device's k/shards-row slab,
    split into panels of the active height (clamped to the slab).  The
    schedule is IDENTICAL on every device — slabs are equal — so a
    non-dividing panel height shortens the last step everywhere at once
    and no step ever pads."""
    slab = k // shards
    rows = min(panel_rows(k) or slab, slab)
    return panel_bounds(slab, rows)


# Fully-resolved configurations whose sharded programs completed one run
# this process — the journal's compile hit/miss signal for the sharded
# rung (da/eds.pipeline_cache_state), keyed like kernels/panel._PANEL_WARM
# plus the shard count.
_SHARDED_WARM: set[tuple] = set()


def is_sharded_warm(k: int, construction: str | None = None) -> bool:
    construction = construction or active_construction()
    n = shards_for_k(k)
    return (k, construction, n, *_resolved_config(k, construction)) \
        in _SHARDED_WARM


def _note_build() -> None:
    from celestia_app_tpu.trace.journal import note_jit_build

    note_jit_build("sharded_panel_pipeline")


def _track(fn, k: int, shards: int, construction: str | None = None,
           h: int | None = None, sub: str = ""):
    """Register one sharded-panel sub-program with the device ledger
    (family sharded_panel_pipeline; the step height rides batch, the
    sub-program name rides the mode column so roots/assemble/leaves do
    not merge into one ledger row)."""
    from celestia_app_tpu.trace.device_ledger import track

    mode = f"sharded_panel/{sub}" if sub else "sharded_panel"
    return track(
        fn, "sharded_panel_pipeline",
        k=k, construction=construction, mode=mode,
        batch=h, shards=shards,
    )


def _parity_ns(shape) -> jnp.ndarray:
    parity = jnp.frombuffer(PARITY_NAMESPACE_BYTES, dtype=jnp.uint8)
    return jnp.broadcast_to(parity, (*shape, NAMESPACE_SIZE))


def _shard_map(f, mesh, in_specs, out_specs):
    # Replication check off: replicated outputs come from collectives the
    # static checker cannot always prove replicated.
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# --- the sharded programs ----------------------------------------------------


@lru_cache(maxsize=None)
def _jit_row_panel_sharded(k: int, h: int, shards: int, construction: str):
    """f(panels (shards*h, k, S) row-sharded) -> (ext (shards*h, 2k, S),
    ns (shards*h, 2k, 29), hashes (shards*h, 2k, 32)), all row-sharded:
    one panel step of the row phase on every device at once — the exact
    kernels/panel._jit_row_panel body inside a collective-free shard_map
    (leaf namespaces depend only on the column inside the top half, so
    the body needs no global row index)."""
    _note_build()
    from jax.sharding import PartitionSpec as P

    mesh = extend_mesh(shards)
    encode = encode_fn(k, construction)

    def local(panel: jnp.ndarray):
        parity = jnp.frombuffer(PARITY_NAMESPACE_BYTES, dtype=jnp.uint8)
        q1 = encode(panel, 1)  # (h, k, S)
        ext = jnp.concatenate([panel, q1], axis=1)  # (h, 2k, S)
        col = jnp.arange(2 * k)
        ns = jnp.where(
            (col < k)[None, :, None], ext[..., :NAMESPACE_SIZE], parity
        )
        _, _, hashes = leaf_digests(ns, ext)
        return ext, ns, hashes

    body = _shard_map(
        local, mesh,
        in_specs=P(EXTEND_AXIS, None, None),
        out_specs=(P(EXTEND_AXIS, None, None),) * 3,
    )
    sh = row_sharding3(mesh, EXTEND_AXIS)
    return _track(
        jax.jit(body, in_shardings=sh, out_shardings=(sh, sh, sh)),
        k, shards, construction, h, sub="row",
    )


def _bounds_from_heights(heights: tuple) -> tuple:
    out, r0 = [], 0
    for h in heights:
        out.append((r0, r0 + h))
        r0 += h
    return tuple(out)


@lru_cache(maxsize=None)
def _step_generator_slices(k: int, construction: str, shards: int,
                           heights: tuple):
    """Per-step SHARDED block-columns of the bit-expanded generator:
    device i's slice for step (r0, r1) is G_bits[:, (i*slab+r0)*m :
    (i*slab+r1)*m] — together across steps and devices they are the same
    bytes the single-device panel runner caches, laid out once with the
    committed row sharding (leading device axis).  Keyed on the panel
    SCHEDULE (`heights`), not the env: a mid-process
    $CELESTIA_PIPE_PANEL flip resolves a new runner, and its slices
    must never alias a stale height's."""
    codec = codec_for_width(k, construction)
    g_bits = codec.generator_bits()
    m = codec.field.m
    slab = k // shards
    out = []
    for r0, r1 in _bounds_from_heights(heights):
        stacked = np.stack([
            g_bits[:, (i * slab + r0) * m: (i * slab + r1) * m]
            for i in range(shards)
        ])  # (shards, k*m, h*m)
        out.append(jax.device_put(
            stacked, row_sharding3(extend_mesh(shards), EXTEND_AXIS)
        ))
    from celestia_app_tpu.trace.device_ledger import note_owned_bytes

    note_owned_bytes(
        "sharded_generator_slices", (k, construction, shards, heights),
        sum(int(s.nbytes) for s in out),
    )
    return tuple(out)


@lru_cache(maxsize=None)
def _jit_zero_acc(k: int, shards: int):
    """The donated parity-row accumulator, born row-sharded: allocating
    it through a committed-out_shardings program (not a host device_put)
    means no host ever materializes the half-EDS zeros."""
    _note_build()
    sh = row_sharding3(extend_mesh(shards), EXTEND_AXIS)
    return _track(
        jax.jit(
            lambda: jnp.zeros((k, 2 * k, SHARE_SIZE), dtype=jnp.uint8),
            out_shardings=sh,
        ),
        k, shards, sub="zero_acc",
    )


@lru_cache(maxsize=None)
def _jit_col_partial_sharded(k: int, h: int, shards: int, construction: str):
    """One step of the sharded column contraction — THE collective
    program of the dense leg.

    f(acc (k, 2k, S) row-sharded [donated], ext (shards*h, 2k, S)
    row-sharded, g (shards, k*m, h*m) row-sharded) -> acc'.

    Every device computes its panel's XOR partial product one OUTPUT
    BLOCK at a time (slab*m generator rows against its h*m local
    columns), the block is XOR all-reduced across the mesh
    (parallel/mesh.xor_allreduce), and only the owning device folds it
    into its accumulator slice — working set one (slab, 2k, S) block,
    never the whole half-EDS."""
    _note_build()
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.kernels.fused import (
        _silence_unusable_donation_warning,
    )

    _silence_unusable_donation_warning()
    mesh = extend_mesh(shards)
    m = codec_for_width(k, construction).field.m
    slab = k // shards

    def local(acc_local, ext_local, g_local):
        # acc_local (slab, 2k, S); ext_local (h, 2k, S);
        # g_local (1, k*m, h*m)
        g = g_local[0]
        idx = lax.axis_index(EXTEND_AXIS)
        for b in range(shards):
            gb = g[b * slab * m: (b + 1) * slab * m, :]
            part = encode_axis(ext_local, gb, m, contract_axis=0)
            red = xor_allreduce(part, EXTEND_AXIS, shards)
            acc_local = jnp.where(idx == b, acc_local ^ red, acc_local)
        return acc_local

    body = _shard_map(
        local, mesh,
        in_specs=(P(EXTEND_AXIS, None, None),) * 3,
        out_specs=P(EXTEND_AXIS, None, None),
    )
    sh = row_sharding3(mesh, EXTEND_AXIS)
    return _track(
        jax.jit(
            body, donate_argnums=(0,),
            in_shardings=(sh, sh, sh), out_shardings=sh,
        ),
        k, shards, construction, h, sub="col",
    )


@lru_cache(maxsize=None)
def _jit_fft_col_sharded(k: int, shards: int, heights: tuple,
                         construction: str, md: bool):
    """The FFT leg's ONE collective program: f(*ext_steps) -> bottom
    (k, 2k, S) row-sharded.

    The butterflies contract over the whole row axis, so each device's
    top slab all_to_alls into a 2k/shards-column block (columns are pure
    batch in the butterfly network — kernels/fft.col_block_encode_fn),
    the block encodes shard-local, and a second all_to_all lands the
    parity rows back on the committed row sharding.  Shares cross the
    interconnect exactly twice; nothing else moves."""
    _note_build()
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.kernels.fft import col_block_encode_fn

    mesh = extend_mesh(shards)
    col_encode = col_block_encode_fn(k, construction, md=md)

    def local(*ext_locals):
        # each (h_j, 2k, S); concatenated = this device's contiguous slab
        top_local = (ext_locals[0] if len(ext_locals) == 1
                     else jnp.concatenate(ext_locals, axis=0))
        top_local = lax.optimization_barrier(top_local)
        cols_blk = lax.all_to_all(
            top_local, EXTEND_AXIS, split_axis=1, concat_axis=0, tiled=True
        )  # (k, 2k/shards, S) — device-major stacking == natural rows
        bottom_cols = col_encode(cols_blk)  # (k, 2k/shards, S)
        bottom_cols = lax.optimization_barrier(bottom_cols)
        return lax.all_to_all(
            bottom_cols, EXTEND_AXIS, split_axis=0, concat_axis=1,
            tiled=True,
        )  # (k/shards, 2k, S)

    body = _shard_map(
        local, mesh,
        in_specs=(P(EXTEND_AXIS, None, None),) * len(heights),
        out_specs=P(EXTEND_AXIS, None, None),
    )
    sh = row_sharding3(mesh, EXTEND_AXIS)
    return _track(
        jax.jit(
            body, in_shardings=(sh,) * len(heights), out_shardings=sh
        ),
        k, shards, construction, sub="fft_col",
    )


@lru_cache(maxsize=None)
def _jit_parity_leaves_sharded(k: int, shards: int):
    """f(bottom (k, 2k, S) row-sharded) -> hashes (k, 2k, 32) row-sharded:
    leaf digests of the all-parity-namespace bottom half, shard-local."""
    _note_build()
    from jax.sharding import PartitionSpec as P

    mesh = extend_mesh(shards)
    slab = k // shards

    def local(block: jnp.ndarray):
        ns = _parity_ns((slab, 2 * k))
        _, _, hashes = leaf_digests(ns, block)
        return hashes

    body = _shard_map(
        local, mesh,
        in_specs=P(EXTEND_AXIS, None, None),
        out_specs=P(EXTEND_AXIS, None, None),
    )
    sh = row_sharding3(mesh, EXTEND_AXIS)
    return _track(jax.jit(body, in_shardings=sh, out_shardings=sh),
                  k, shards, sub="parity_leaves")


@lru_cache(maxsize=None)
def _natural_perm(k: int, shards: int, heights: tuple) -> tuple:
    """Static permutation from step-major stacking to natural row order.

    The per-step sharded outputs concatenate (step-major, then
    device-major, then row); natural ODS row i*slab + r0_j + r sits at
    stacked position (steps offset j) + i*h_j + r.  Pure layout math,
    keyed on the panel schedule (the env can re-resolve it
    mid-process)."""
    bounds = _bounds_from_heights(heights)
    slab = k // shards
    perm = np.empty(k, dtype=np.int32)
    off = 0
    for (r0, r1) in bounds:
        h = r1 - r0
        for i in range(shards):
            rows = np.arange(h)
            perm[i * slab + r0 + rows] = off + i * h + rows
        off += shards * h
    return tuple(int(x) for x in perm)


def _take_natural(steps, perm):
    x = (steps[0] if len(steps) == 1
         else jnp.concatenate(steps, axis=0))
    if perm == tuple(range(len(perm))):
        return x
    return jnp.take(x, jnp.asarray(perm, dtype=jnp.int32), axis=0)


@lru_cache(maxsize=None)
def _jit_roots_sharded(k: int, shards: int, heights: tuple):
    """f(*ns_steps, *hash_steps, bot_hashes) -> (row_roots, col_roots,
    droot), replicated: the digest grids reassemble in natural row order
    (static permutation), all_gather under the committed replicated
    out_shardings — the MULTICHIP subtree-root shape — and the tree
    reduction runs replicated, identical to kernels/panel's
    _jit_panel_roots over the same digests."""
    _note_build()
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = extend_mesh(shards)
    perm = _natural_perm(k, shards, heights)
    n_steps = len(heights)

    def replicated(*args):
        ns_steps = args[:n_steps]
        hash_steps = args[n_steps:2 * n_steps]
        bot_hashes = args[2 * n_steps]
        top_ns = _take_natural(ns_steps, perm)  # (k, 2k, 29)
        top_hashes = _take_natural(hash_steps, perm)  # (k, 2k, 32)
        ns = jnp.concatenate([top_ns, _parity_ns((k, 2 * k))], axis=0)
        hashes = jnp.concatenate([top_hashes, bot_hashes], axis=0)
        row_roots = tree_roots_from_digests(ns, ns, hashes)  # (2k, 90)
        nst = ns.transpose(1, 0, 2)
        col_roots = tree_roots_from_digests(
            nst, nst, hashes.transpose(1, 0, 2)
        )
        droot = merkle_root_pow2(
            jnp.concatenate([row_roots, col_roots], axis=0)
        )
        return row_roots, col_roots, droot

    # The tree hashes select the Pallas SHA kernel at these batch sizes,
    # and GSPMD cannot partition a Mosaic kernel: run the reduction as a
    # per-device body over the all-gathered digests (replicated in,
    # replicated out — the same bytes on every device).
    run = _shard_map(
        replicated, mesh, in_specs=(P(),) * (2 * n_steps + 1),
        out_specs=(P(), P(), P()),
    )
    sh = row_sharding3(mesh, EXTEND_AXIS)
    rep = NamedSharding(mesh, P())
    return _track(
        jax.jit(
            run,
            in_shardings=(sh,) * (2 * n_steps + 1),
            out_shardings=(rep, rep, rep),
        ),
        k, shards, sub="roots",
    )


@lru_cache(maxsize=None)
def _jit_eds_assemble(k: int, shards: int, heights: tuple):
    """f(*ext_steps, bottom) -> eds (2k, 2k, S) under THE committed row
    sharding (parallel/mesh.row_sharding3) — the one layout commit of the
    whole pipeline.  GSPMD lowers the natural-order gather across shards
    (this is the distributed twin of the panel runner's final
    concatenate); everything downstream — retention, the serve share
    gather — names this sharding back and never moves a byte."""
    _note_build()
    mesh = extend_mesh(shards)
    perm = _natural_perm(k, shards, heights)
    n_steps = len(heights)

    def run(*args):
        ext_steps = args[:n_steps]
        bottom = args[n_steps]
        top = _take_natural(ext_steps, perm)  # (k, 2k, S)
        return jnp.concatenate([top, bottom], axis=0)

    sh = row_sharding3(mesh, EXTEND_AXIS)
    return _track(
        jax.jit(
            run, in_shardings=(sh,) * (n_steps + 1), out_shardings=sh
        ),
        k, shards, sub="assemble",
    )


# --- the runner --------------------------------------------------------------


def sharded_panel_pipeline(k: int, construction: str | None = None,
                           roots_only: bool = False):
    """The sharded panel-streamed pipeline callable for square size k.

    Same surface as kernels/panel.panel_pipeline: f(ods) ->
    (eds, row_roots, col_roots, droot) or the roots_only twin — with the
    EDS returned ROW-SHARDED across the extend mesh under
    parallel/mesh.row_sharding3 (roots replicated, read as host bytes
    like any other lowering's).  `ods` is the (k, k, S) array (host
    numpy uploads one panel step at a time, each step already laid out
    row-sharded).

    Host-driven like the single-device runner: every dispatch passes the
    chaos device.dispatch seam under mode "sharded_panel" AND the NEW
    device.extend_shard seam ($CELESTIA_CHAOS extend_shard_fail=p), so
    an injected mid-collective fault surfaces to guarded_dispatch and
    walks the ladder down to the single-device panel rung.
    """
    construction = construction or active_construction()
    shards = shards_for_k(k)
    if not shards:
        raise ValueError(
            f"sharded panel mode not engaged for k={k} "
            f"(CELESTIA_EXTEND_SHARDS={os.environ.get('CELESTIA_EXTEND_SHARDS')!r}, "
            f"CELESTIA_PIPE_PANEL={os.environ.get('CELESTIA_PIPE_PANEL')!r})"
        )
    rows, use_fft, md = _resolved_config(k, construction)
    return _sharded_runner(k, construction, roots_only, shards, rows,
                           use_fft, md)


@lru_cache(maxsize=None)
def _sharded_runner(k: int, construction: str, roots_only: bool,
                    shards: int, rows: int, use_fft: bool, md: bool):
    # The schedule derives from the CACHE KEY (`rows`), never the live
    # env: a $CELESTIA_PIPE_PANEL flip resolves a different runner, and
    # this one keeps the bounds it was built for.
    slab = k // shards
    bounds = panel_bounds(slab, min(rows or slab, slab))
    heights = tuple(r1 - r0 for r0, r1 in bounds)
    sh3 = row_sharding3(extend_mesh(shards), EXTEND_AXIS)

    def _seams():
        from celestia_app_tpu import chaos

        chaos.device_dispatch("sharded_panel")
        chaos.extend_shard()

    def run(x):
        if isinstance(x, (list, tuple)):
            raise ValueError(
                "sharded panel mode takes the whole (k, k, S) ODS "
                "(panel staging is the runner's own slab layout)"
            )
        if x.shape != (k, k, SHARE_SIZE):
            raise ValueError(f"bad ODS shape {x.shape} for k={k}")
        ods = x if isinstance(x, np.ndarray) else np.asarray(x)
        ext_steps: list = []
        ns_steps: list = []
        hash_steps: list = []
        acc = None
        g_steps = None
        if not use_fft:
            g_steps = _step_generator_slices(k, construction, shards,
                                             heights)
            _seams()
            acc = _jit_zero_acc(k, shards)()
        for j, (r0, r1) in enumerate(bounds):
            h = r1 - r0
            _seams()
            stacked = np.concatenate([
                ods[i * slab + r0: i * slab + r1] for i in range(shards)
            ], axis=0)
            panel_dev = jax.device_put(
                np.ascontiguousarray(stacked, dtype=np.uint8), sh3
            )
            ext, ns, hashes = _jit_row_panel_sharded(
                k, h, shards, construction
            )(panel_dev)
            ns_steps.append(ns)
            hash_steps.append(hashes)
            if not use_fft:
                _seams()
                acc = _jit_col_partial_sharded(
                    k, h, shards, construction
                )(acc, ext, g_steps[j])
            if use_fft or not roots_only:
                ext_steps.append(ext)
        if use_fft:
            _seams()
            bottom = _jit_fft_col_sharded(
                k, shards, heights, construction, md
            )(*ext_steps)
        else:
            bottom = acc
        _seams()
        bot_hashes = _jit_parity_leaves_sharded(k, shards)(bottom)
        _seams()
        row_roots, col_roots, droot = _jit_roots_sharded(
            k, shards, heights
        )(*ns_steps, *hash_steps, bot_hashes)
        _SHARDED_WARM.add((k, construction, shards, rows, use_fft, md))
        if roots_only:
            return row_roots, col_roots, droot
        _seams()
        eds = _jit_eds_assemble(k, shards, heights)(*ext_steps, bottom)
        return eds, row_roots, col_roots, droot

    return run


def sharded_panel_count(k: int) -> int:
    """Panel STEPS the sharded seam would stream for square size k (each
    step is one mesh-wide dispatch); 0 when the sharded seam is off."""
    n = shards_for_k(k)
    return len(local_panel_bounds(k, n)) if n else 0
