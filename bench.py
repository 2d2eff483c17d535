"""Benchmark: MB/s erasure-extended + DAH-hashed per chip (BASELINE.json metric).

Measures the fused device pipeline (RS 2D extension + 4k NMT roots + DAH data
root; reference hot path app/prepare_proposal.go:61-71) end to end — host
ODS in, data root back on host — at k=128/256/512 plus the repair and
streamed modes, and compares against the in-image host path.

Prints ONE JSON line:
  {"metric": ..., "value": MB/s, "unit": "MB/s", "vs_baseline": x, ...}
extra keys: "platform", "results" (all completed stages), "baseline_note",
"errors".

Chip only: the measurement runs in ONE child process (the parent never
imports jax, so the child is the one process that holds the chip).  A
child that finds no TPU records why and the bench exits non-zero with no
rate printed — a CPU run is never reported under a per-chip metric.
Every row carries the device it ran on (`platform`, `device_kind`,
`n_devices`).  The child appends one JSON line per completed stage to a
results file, so a mid-run hang leaves the earlier numbers intact and the
parent still emits an honest summary line.

Env knobs:
  BENCH_K            run only this square size (default: 128, 256, 512;
                     giant sizes 1024/2048/4096 are accepted here — the
                     default k-list is unchanged — and scale their own
                     iteration counts / host-RAM prebuild down; a comma
                     list runs a multi-k sweep in one record)
  BENCH_MODE         run only this mode: extend | compute | repair |
                     stream | compute_sharded (the multi-chip extend
                     sweep: one row per BENCH_SHARDS count over an
                     identical sharded-panel plan, kernels/panel_sharded)
                     | mempool (the concurrent-broadcast admission A/B:
                     BENCH_THREADS threads drive a whale+small+spammer
                     tenant mix through PriorityMempool.insert, sharded
                     [$CELESTIA_MEMPOOL_SHARDS stripes] vs the frozen
                     global-lock baseline rung — no device needed)
  BENCH_SHARDS       compute_sharded sweep shard counts (default "1,8")
  BENCH_THREADS      mempool A/B concurrent broadcast threads (default 8)
  BENCH_MEMPOOL_TXS  mempool A/B txs per thread per leg (default 32)
  BENCH_MEMPOOL_ITERS mempool A/B leg repetitions, best-of (default 3)
  BENCH_ITERS        timed iterations (default 5; 2 at k>=256)
  BENCH_BASELINE_S   skip the host-baseline run, use the given seconds/block
  BENCH_TOTAL_BUDGET wall-clock budget in seconds (default 1500)

Observability: every completed stage row is also written into the trace
layer's tables (table "bench_rows", the same tracer the serving planes
export over GET /trace_tables), and `--metrics-out <dir>` (or
BENCH_METRICS_OUT) additionally writes `bench_metrics.prom` — a Prometheus
textfile-collector exposition of the per-row rates — plus
`bench_rows.jsonl` next to the BENCH_*.json summary.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))

BASELINE_NOTE = (
    "headline value is the device-resident (`compute`) rate at k=512, the "
    "north-star square size (BASELINE.json). host baseline is the in-image "
    "single-core numpy-GF + hashlib-SHA256 path at k=128 "
    "(testutil/reference.host_dah); the reference's Go leopard SIMD + "
    "SHA-NI codec is not runnable in this image (no Go toolchain), so "
    "vs_baseline (a rate ratio) overstates the margin vs the real "
    "reference CPU path. The extend/stream/repair modes include the "
    "host<->device link; the `compute` rows isolate the on-chip pipeline "
    "rate. compute@512 runs twice (stability_pct = spread between the two "
    "medians). Every timed iteration of every row uses a DISTINCT input, "
    "so no cached (executable, args) result is ever what gets measured. "
    "The `parts` row decomposes compute@512 into rs_dense / rs_fft / "
    "rs_fft_md / rs_dense_pl (fused Pallas dense, TPU only) / rs_xor "
    "(bitsliced XOR/AND-parity planes, TPU only) and nmt_dah_{jnp,pallas} "
    "device seconds, plus `fused` and `fused_epi` rows: the single-"
    "dispatch extend_and_dah program (kernels/fused, ODS buffer donated) "
    "and its leaf-hash-epilogue variant (kernels/rs_xor), A/B'd against "
    "the seated staged extend+hash pair. The parts row doubles as the "
    "autotuner: it runs first and every later row rides the fastest "
    "measured RS and SHA lowerings and the winning fused-vs-staged "
    "pipeline (defaults keep the seat unless a challenger is >3% faster; "
    "the chosen config is recorded in the parts row's `tuned` field). "
    "Stream mode double-buffers with a dedicated uploader thread and a "
    "separate dispatcher (block N+1 uploads while block N computes); its "
    "stream_b{1,2,4} rows coalesce B same-k squares into ONE vmapped "
    "dispatch ($CELESTIA_PIPE_BATCH): batch-B seconds/block below the "
    "batch-1 row means B squares in one dispatch cost less than B "
    "dispatch latencies."
)


def _random_ods(k: int, seed: int = 3) -> np.ndarray:
    from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE

    rng = np.random.default_rng(seed)
    n = k * k
    ns = np.sort(rng.integers(0, 200, n).astype(np.uint8))
    ods = rng.integers(0, 256, (n, SHARE_SIZE), dtype=np.uint8)
    ods[:, :NAMESPACE_SIZE] = 0
    ods[:, NAMESPACE_SIZE - 1] = ns
    return ods.reshape(k, k, SHARE_SIZE)


# --------------------------------------------------------------------------
# measurement stages (run inside the child process only)
# --------------------------------------------------------------------------


def _median(times: list[float]) -> float:
    return sorted(times)[len(times) // 2]


def _variant(ods: np.ndarray, i: int, axis: int = 1) -> np.ndarray:
    """The i-th distinct input derived from `ods` (i >= 0 never equals the
    warmup array).  Every timed iteration must see a DISTINCT input: a
    repeat (executable, args) pair has been observed served from a cache
    (a parts run returned 0.0s for a 128 MB-output program) — reusing a
    buffer can measure a cache instead of the link or the chip."""
    return np.ascontiguousarray(np.roll(ods, i + 1, axis=axis))


def _extend_seconds(ods: np.ndarray, iters: int) -> float:
    """Full offload round trip: host ODS -> device pipeline -> host data root.

    Every iteration uploads a DISTINCT array (see _variant; round-3
    VERDICT weak #3)."""
    from celestia_app_tpu.da.eds import ExtendedDataSquare

    variants = [_variant(ods, i, axis=0) for i in range(iters)]
    ExtendedDataSquare.compute(ods).data_root()  # warmup / compile
    times = []
    for i in range(iters):
        t0 = time.perf_counter()
        ExtendedDataSquare.compute(variants[i]).data_root()
        times.append(time.perf_counter() - t0)
    return _median(times)


def _compute_seconds(ods: np.ndarray, iters: int) -> float:
    """Device-resident pipeline rate: shares already in HBM, full fused
    extend+NMT+DAH program, data root back to host.  Isolates the chip's
    compute from the host link (`extend` adds the upload).  Median of per-iteration times — round-2's
    driver run recorded a 25x load-induced collapse off a plain 2-iter
    mean, so each iteration is timed separately and the median reported."""
    import jax
    import jax.numpy as jnp

    from celestia_app_tpu.da.eds import jit_pipeline

    k = ods.shape[0]
    pipe = jit_pipeline(k)
    xs = [jax.device_put(jnp.asarray(_variant(ods, i))) for i in range(iters)]
    warm = jax.device_put(jnp.asarray(ods))
    jax.block_until_ready(xs)
    np.asarray(pipe(warm)[3])  # warmup / compile
    times = []
    for i in range(iters):
        t0 = time.perf_counter()
        np.asarray(pipe(xs[i])[3])
        times.append(time.perf_counter() - t0)
    return _median(times)


def _sharded_shard_counts() -> list[int]:
    """$BENCH_SHARDS: the compute_sharded sweep's shard counts (default
    "1,8" — the forced-host 1-vs-N machinery curve; real-chip rounds pick
    the mesh widths the hardware has)."""
    raw = os.environ.get("BENCH_SHARDS", "1,8")
    counts = []
    for tok in raw.replace(",", " ").split():
        try:
            n = int(tok)
        except ValueError:
            # Loud, not silent (the CELESTIA_EXTEND_SHARDS convention):
            # a typo'd sweep collapsing to the 1-shard control would
            # read downstream as an opt-in plan gap, hiding the loss.
            print(f"bench: ignoring malformed BENCH_SHARDS entry {tok!r}",
                  file=sys.stderr)
            continue
        if n >= 1:
            counts.append(n)
    return counts or [1]


def _compute_sharded_seconds(ods: np.ndarray, iters: int, shards: int
                             ) -> tuple[float, int]:
    """One compute_sharded sweep leg: seconds/block through the sharded
    panel pipeline at `shards` devices (shards=1 = the single-device
    panel runner, the control every wider leg is judged against).

    The PLAN is identical per shard count — same panel height, same
    DISTINCT per-iteration inputs, same host-driven compute() entry (the
    PR 13 das-v2 sweep pattern applied to the write side) — so the curve
    measures the mesh, not a workload difference.  Returns the ACTUAL
    shard count the seam engaged with (clamped like the serve plane's),
    so rows are keyed by what ran, not what was asked."""
    from celestia_app_tpu.da.eds import ExtendedDataSquare
    from celestia_app_tpu.kernels.fused import pipeline_mode_for_k
    from celestia_app_tpu.kernels.panel_sharded import shards_for_k

    k = ods.shape[0]
    os.environ["CELESTIA_EXTEND_SHARDS"] = (
        str(shards) if shards > 1 else "0"
    )
    actual = shards_for_k(k) or 1
    expect = "sharded_panel" if actual > 1 else "panel"
    mode = pipeline_mode_for_k(k)
    if mode != expect:
        raise RuntimeError(
            f"compute_sharded leg resolved mode {mode!r}, want {expect!r} "
            f"(shards={shards}, actual={actual})"
        )
    variants = [_variant(ods, i) for i in range(iters)]
    ExtendedDataSquare.compute(ods).data_root()  # warmup / compile
    times = []
    for i in range(iters):
        t0 = time.perf_counter()
        ExtendedDataSquare.compute(variants[i]).data_root()
        times.append(time.perf_counter() - t0)
    return _median(times), actual


def _host_seconds_per_block(ods: np.ndarray) -> float:
    """Host path: numpy GF RS extension + hashlib SHA-256 NMT trees
    (testutil/reference.host_dah, single core) — a stand-in for the
    reference's Go leopard + crypto/sha256 path, which is faster; see
    BASELINE_NOTE."""
    from celestia_app_tpu.testutil.reference import host_dah

    t0 = time.perf_counter()
    host_dah(ods)
    return time.perf_counter() - t0


def _parts_seconds(ods: np.ndarray, iters: int) -> dict:
    """Decomposition of the fused pipeline at one k: device-resident times
    for the RS extension under all three encode lowerings (dense generator
    matmul, additive-FFT stage groups, transpose-free FFT) and for the
    NMT+DAH hashing half under both SHA paths (fused-jnp vs Pallas).

    Doubles as the AUTOTUNER: the returned dict carries a "tuned" entry
    naming the fastest RS and SHA variants; the bench child applies those
    to every later stage, so the headline compute rows always ride the
    best lowering this chip measured (a >3% margin is required to leave
    the defaults — noise must not flip the config)."""
    import jax
    import jax.numpy as jnp

    from celestia_app_tpu.da.eds import roots_fn
    from celestia_app_tpu.kernels.rs import extend_square_fn

    k = ods.shape[0]
    x = jax.device_put(jnp.asarray(ods))
    xs = [jax.device_put(jnp.asarray(_variant(ods, i))) for i in range(iters)]
    out: dict[str, float] = {}
    eds = None
    try:
        on_tpu = jax.devices()[0].platform == "tpu"
    except Exception:  # noqa: BLE001
        on_tpu = False
    saved = {
        var: os.environ.get(var)
        for var in ("CELESTIA_RS_FFT", "CELESTIA_RS_FFT_MD",
                    "CELESTIA_RS_PALLAS", "CELESTIA_RS_XOR")
    }
    try:
        # Each variant builds a FRESH jax.jit around extend_square_fn, so
        # the env flags are re-read at trace time (the lru-cached module
        # wrappers key on (k, construction) only and must not be used for
        # an A/B like this — they would serve the first trace twice).
        variants = [
            ("rs_fft", {"CELESTIA_RS_FFT": "on", "CELESTIA_RS_FFT_MD": ""}),
            ("rs_fft_md", {"CELESTIA_RS_FFT": "on", "CELESTIA_RS_FFT_MD": "1"}),
            ("rs_dense", {"CELESTIA_RS_FFT": "off", "CELESTIA_RS_FFT_MD": ""}),
        ]
        if on_tpu:  # the Pallas kernels have no compiled CPU path
            from celestia_app_tpu.gf.rs import codec_for_width
            from celestia_app_tpu.kernels.rs_pallas import pallas_supported
            from celestia_app_tpu.kernels.rs_xor import xor_supported

            m_field = codec_for_width(k).field.m
            if pallas_supported(k, m_field):
                variants.append(
                    ("rs_dense_pl",
                     {"CELESTIA_RS_FFT": "off", "CELESTIA_RS_FFT_MD": "",
                      "CELESTIA_RS_PALLAS": "on"}))
            if xor_supported(k, m_field):
                variants.append(
                    ("rs_xor",
                     {"CELESTIA_RS_FFT": "off", "CELESTIA_RS_FFT_MD": "",
                      "CELESTIA_RS_XOR": "on"}))
        for label, flags in variants:
            os.environ.pop("CELESTIA_RS_PALLAS", None)
            os.environ.pop("CELESTIA_RS_XOR", None)
            for var, val in flags.items():
                if val:
                    os.environ[var] = val
                else:
                    os.environ.pop(var, None)
            # Per-candidate guard: an opt-in kernel that fails to COMPILE
            # on this chip (the Pallas candidates are exactly the ones
            # unmeasured on hardware) must cost its own row, not the
            # whole parts stage — the incumbents' times and the autotune
            # seat survive.  rs_dense is the incumbent and must raise.
            try:
                fn = jax.jit(extend_square_fn(k))
                eds = fn(x)
                jax.block_until_ready(eds)
                times = []
                for i in range(iters):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(xs[i]))
                    times.append(time.perf_counter() - t0)
                out[label] = _median(times)
            except Exception as e:  # noqa: BLE001 — challenger-only tolerance
                if label == "rs_dense":
                    raise
                out[f"{label}_error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        # Restore even when a stage raises: a leaked =on would silently
        # flip every later bench stage onto the non-default FFT path.
        for var, val in saved.items():
            if val is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = val
    # SHA A/B over the hashing half.  Distinct EDS per iteration (extend
    # the distinct inputs on the restored default path), produced one at a
    # time so only one extra (2k,2k,S) square is ever live in HBM
    # alongside the one being hashed.  Release the warmup square and the
    # A/B input before the loop.
    del eds
    del x
    ext = jax.jit(extend_square_fn(k))
    sha_rows = [("nmt_dah_jnp", {"CELESTIA_SHA_PALLAS": "off",
                                 "CELESTIA_SHA_FUSED": "off"})]
    if on_tpu:  # the Pallas kernels have no compiled CPU path
        sha_rows.append(("nmt_dah_pallas", {"CELESTIA_SHA_PALLAS": "on",
                                            "CELESTIA_SHA_FUSED": "off"}))
        # plf: fused-leaf kernel (message construction in VMEM) for the
        # leaf level + the lane-parallel kernel for node levels.
        sha_rows.append(("nmt_dah_plf", {"CELESTIA_SHA_PALLAS": "on",
                                         "CELESTIA_SHA_FUSED": "on"}))
    saved_sha = {v: os.environ.get(v)
                 for v in ("CELESTIA_SHA_PALLAS", "CELESTIA_SHA_FUSED")}
    try:
        for row_i, (label, flags) in enumerate(sha_rows):
            os.environ.update(flags)
            hash_fn = jax.jit(roots_fn(k))
            # Warm on an input DISTINCT from every timed xs[i] (base past
            # the timed range, one per row) — warming on xs[0] would make
            # iteration 0 a repeat (executable, args) pair, the exact
            # hazard _variant documents.
            warm_x = jax.device_put(jnp.asarray(_variant(ods, iters + row_i)))
            warm_eds = ext(warm_x)
            jax.block_until_ready(hash_fn(warm_eds))
            del warm_eds, warm_x
            times = []
            for i in range(iters):
                eds_i = ext(xs[i])
                jax.block_until_ready(eds_i)
                t0 = time.perf_counter()
                jax.block_until_ready(hash_fn(eds_i))
                times.append(time.perf_counter() - t0)
                del eds_i
            out[label] = _median(times)
    finally:
        _apply_env(saved_sha)
    out["nmt_dah"], tuned = _pick_tuned(out, on_tpu)
    # Fused single-dispatch candidates: the whole extend+NMT+DAH program
    # as ONE executable with the ODS buffer donated (kernels/fused) plus
    # its leaf-hash-epilogue variant (fused_epi), both timed under the
    # tuner's RS/SHA picks so the A/B against the seated staged pair is
    # like-for-like.  A fused-only fault must not discard the completed
    # staged rows, so each degrades to a note instead of raising.
    try:
        out["fused"] = _fused_seconds(ods, iters, tuned)
        try:
            out["fused_epi"] = _fused_seconds(ods, iters, tuned,
                                              epilogue=True)
        except Exception as e:  # noqa: BLE001 — epi is optional, fused is not
            out["fused_epi_error"] = f"{type(e).__name__}: {e}"[:200]
        tuned["pipe"] = _pick_pipe(out, tuned)
    except Exception as e:  # noqa: BLE001 — keep the staged measurement
        out["fused_error"] = f"{type(e).__name__}: {e}"[:200]
    out["tuned"] = tuned
    return out


_TUNE_VARS = (
    "CELESTIA_RS_FFT", "CELESTIA_RS_FFT_MD", "CELESTIA_RS_PALLAS",
    "CELESTIA_RS_XOR", "CELESTIA_SHA_PALLAS", "CELESTIA_SHA_FUSED",
    "CELESTIA_PIPE_FUSED",
)


def _env_for_tuned(tuned: dict) -> dict:
    """Env assignment that makes the library run the tuner's picks.

    Values of None mean "remove the var".  Shared by the in-parts fused
    timing and the child's apply step so the two can never disagree about
    what a pick means."""
    env: dict = {"CELESTIA_RS_FFT": "off", "CELESTIA_RS_FFT_MD": None,
                 "CELESTIA_RS_PALLAS": None, "CELESTIA_RS_XOR": None}
    if tuned["rs"] in ("rs_fft", "rs_fft_md"):
        env["CELESTIA_RS_FFT"] = "on"
        if tuned["rs"] == "rs_fft_md":
            env["CELESTIA_RS_FFT_MD"] = "1"
    elif tuned["rs"] == "rs_dense_pl":
        env["CELESTIA_RS_PALLAS"] = "on"
    elif tuned["rs"] == "rs_xor":
        env["CELESTIA_RS_XOR"] = "on"
    env["CELESTIA_SHA_PALLAS"] = (
        "on" if tuned["sha"] in ("pallas", "plf") else "off"
    )
    env["CELESTIA_SHA_FUSED"] = "on" if tuned["sha"] == "plf" else "off"
    if "pipe" in tuned:
        env["CELESTIA_PIPE_FUSED"] = {
            "staged": "off", "fused_epi": "epi"
        }.get(tuned["pipe"], "on")
    return env


def _applied_from_env() -> dict:
    """What the library will ACTUALLY run under the current env — the
    inverse of _env_for_tuned after operator-set knobs are honored.  The
    child's tuned-applied record and the seat-application regression
    tests both call this, so the two directions of the mapping can never
    fork (bench.py:350's shared-mapping contract, extended to rs_xor and
    the fused_epi pipe seat)."""
    fft_env = os.environ.get("CELESTIA_RS_FFT", "auto")
    if fft_env == "on":
        rs = (
            "rs_fft_md"
            if os.environ.get("CELESTIA_RS_FFT_MD") == "1"
            else "rs_fft"
        )
    elif os.environ.get("CELESTIA_RS_PALLAS") == "on":
        rs = "rs_dense_pl"
    elif os.environ.get("CELESTIA_RS_XOR") == "on":
        rs = "rs_xor"
    else:
        rs = "rs_dense"
    sha_env = os.environ.get("CELESTIA_SHA_PALLAS", "auto")
    sha = {"on": "pallas", "off": "jnp"}.get(sha_env, "auto")
    if sha == "pallas" and os.environ.get("CELESTIA_SHA_FUSED") == "on":
        sha = "plf"
    pipe = {"off": "staged", "epi": "fused_epi"}.get(
        os.environ.get("CELESTIA_PIPE_FUSED", "auto"), "fused"
    )
    return {"rs": rs, "sha": sha, "pipe": pipe}


def _apply_env(env: dict) -> None:
    for var, val in env.items():
        if val is None:
            os.environ.pop(var, None)
        else:
            os.environ[var] = val


def _fused_seconds(
    ods: np.ndarray, iters: int, tuned: dict, epilogue: bool = False
) -> float:
    """Device seconds for the fused extend_and_dah program with the ODS
    donated (epilogue=True times the leaf-hash-epilogue variant — the
    fused_epi pipe candidate).  Fresh jax.jit (not the lru-cached module
    wrapper) so the tuned env flags are re-read at trace time; a DISTINCT
    pre-uploaded input per iteration (donation consumes each buffer,
    which also keeps the repeat-input hazard away — see _variant)."""
    import jax
    import jax.numpy as jnp

    from celestia_app_tpu.kernels.fused import (
        _silence_unusable_donation_warning,
        extend_and_dah_fn,
    )

    k = ods.shape[0]
    _silence_unusable_donation_warning()  # CPU: donation noise, not signal
    saved = {v: os.environ.get(v) for v in _TUNE_VARS}
    try:
        _apply_env(_env_for_tuned(tuned))
        fn = jax.jit(
            extend_and_dah_fn(k, epilogue=epilogue), donate_argnums=(0,)
        )
        warm = jax.device_put(jnp.asarray(_variant(ods, iters)))
        jax.block_until_ready(fn(warm))  # warmup / compile (consumes warm)
        times = []
        for i in range(iters):
            x = jax.device_put(jnp.asarray(_variant(ods, i)))
            jax.block_until_ready(x)
            t0 = time.perf_counter()
            out = fn(x)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
            del out  # one EDS live at a time
        return _median(times)
    finally:
        _apply_env(saved)


def _pick_pipe(seconds: dict, tuned: dict) -> str:
    """Pipeline seat with the same >3% hysteresis as _pick_tuned.

    The fused single-dispatch program is the incumbent (the library
    default); the staged extend+hash pair — at its own tuned-best RS and
    SHA lowerings — must beat it by >3% to take the seat, and the
    leaf-hash-epilogue variant (fused_epi) must then beat whichever of
    those holds it by the same margin.  Challenger order is fixed, so a
    noise-level three-way tie always resolves to the incumbent."""
    staged = seconds[tuned["rs"]] + seconds["nmt_dah"]
    best, best_s = "fused", seconds["fused"]
    if staged < 0.97 * best_s:
        best, best_s = "staged", staged
    epi = seconds.get("fused_epi")
    if epi is not None and epi < 0.97 * best_s:
        best = "fused_epi"
    return best


def _pick_tuned(seconds: dict, on_tpu: bool) -> tuple[float, dict]:
    """Winner selection with hysteresis over a parts measurement.

    The incumbents — rs_dense, and the path sha auto would pick on this
    platform (Pallas on TPU, jnp elsewhere) — keep the seat unless a
    challenger is >3% faster, so measurement noise cannot flip the
    config.  Returns (nmt_dah headline seconds — the tuner's SHA pick;
    the child's "tuned-applied" record says what later rows actually ran
    once operator-set knobs are honored, tuned choices dict)."""
    rs_best = "rs_dense"
    for label in ("rs_fft", "rs_fft_md", "rs_dense_pl", "rs_xor"):
        if label in seconds and seconds[label] < 0.97 * seconds[rs_best]:
            rs_best = label
    sha_best = "pallas" if on_tpu else "jnp"
    for label in ("jnp", "plf"):
        key = f"nmt_dah_{label}"
        if key in seconds and seconds[key] < 0.97 * seconds[f"nmt_dah_{sha_best}"]:
            sha_best = label
    return seconds[f"nmt_dah_{sha_best}"], {"rs": rs_best, "sha": sha_best}


def _repair_seconds(ods: np.ndarray, iters: int) -> float:
    """BASELINE config 4: quadrant erasure -> repair -> verified roots."""
    import jax

    from celestia_app_tpu.da import DataAvailabilityHeader, ExtendedDataSquare, repair

    k = ods.shape[0]
    present = np.ones((2 * k, 2 * k), dtype=bool)
    present[k:, k:] = False  # 25% missing

    def damaged_case(o: np.ndarray):
        eds = ExtendedDataSquare.compute(o)
        dah = DataAvailabilityHeader.from_eds(eds)
        full = np.asarray(eds.squared())
        return np.where(present[..., None], full, 0).astype(np.uint8), dah

    warm_damaged, warm_dah = damaged_case(ods)
    repair(warm_damaged, present, warm_dah)  # warmup
    del warm_damaged
    # Distinct (square, DAH) per timed iteration (see _variant), built one
    # at a time so host residency stays at one damaged square; median of
    # per-iteration times like the other rows.
    times = []
    for i in range(iters):
        damaged, dah = damaged_case(_variant(ods, i))
        t0 = time.perf_counter()
        repair(damaged, present, dah)
        jax.effects_barrier()
        times.append(time.perf_counter() - t0)
        del damaged
    return _median(times)


def _stream_block_budget(ods: np.ndarray, iters: int) -> tuple[int, int]:
    """(timed blocks, warm blocks) the stream stages may prebuild under
    the ~1.5 GB host-RAM cap.  The old fixed floor of 4 blocks OVERRAN
    the cap at giant k (4 x 550 MB at k=1024); now the block count
    scales down with the square size — to a floor of one timed block and
    one warm block, the least a stream can stream."""
    cap = int(1.5e9 // ods.nbytes)
    n = max(1, min(4 * iters, cap if cap >= 1 else 1))
    return n, (2 if n >= 4 else 1)


def _stream_seconds(ods: np.ndarray, iters: int) -> float:
    """BASELINE config 5: pipelined block stream — double-buffered async
    dispatch.  The pipeline's uploader thread transfers block i+1 while
    the device computes block i (a separate dispatcher thread keeps the
    upload lane free of dispatch round-trips), so steady state approaches
    max(transfer, compute) instead of their sum, and with the fused
    lowering each uploaded ODS buffer is donated to its dispatch."""
    from celestia_app_tpu.parallel.pipeline import stream_blocks

    k = ods.shape[0]

    # Every streamed block is DISTINCT (see _variant): a cyclic reuse of a
    # few buffers would repeat (executable, args) pairs that a cache
    # can short-circuit, understating the link cost.  All variants
    # are materialized BEFORE the timed window so the feeder never charges
    # host roll/copy work to the stream measurement (device timings
    # collapse badly under concurrent host load on this box).  Prebuilt
    # bytes are capped at ~1.5 GB host RAM (a manual BENCH_K=512 stream
    # would otherwise resident 4*iters 134 MB squares at once); at giant
    # k the cap SCALES THE BLOCK COUNT DOWN (floor 1 — one ODS must
    # exist to stream) instead of overrunning it with a fixed minimum.
    n, warm_n = _stream_block_budget(ods, iters)
    warm_blocks = [_variant(ods, n + i, axis=0) for i in range(warm_n)]
    blocks = [_variant(ods, i, axis=0) for i in range(n)]

    def feed(blist):
        for i, b in enumerate(blist):
            yield i, b

    list(stream_blocks(feed(warm_blocks), k, depth=2))  # warm the pipeline
    t0 = time.perf_counter()
    for _tag, eds in stream_blocks(feed(blocks), k, depth=2):
        eds.data_root()  # host sync per block, as a server would
    return (time.perf_counter() - t0) / n


#: Coalesced-dispatch sizes the batched stream row measures (the
#: continuous-batching leg: B same-k squares in ONE vmapped dispatch
#: instead of B dispatch latencies).
STREAM_BATCHES = (1, 2, 4)


def _stream_batched_seconds(ods: np.ndarray, iters: int) -> dict[int, float]:
    """Seconds per block streamed at each coalescing size in
    STREAM_BATCHES — the blocks/sec face of cross-height continuous
    batching.  Same distinct-buffer and prebuilt-variant rules as
    _stream_seconds; depth widens with the batch so the coalescer always
    has queued squares to merge (otherwise the occupancy signal would
    close every batch at 1 and the row would measure nothing)."""
    from celestia_app_tpu.parallel.pipeline import stream_blocks

    k = ods.shape[0]
    n, _ = _stream_block_budget(ods, iters)
    n -= n % max(STREAM_BATCHES)  # same block count for every batch size
    if n < max(STREAM_BATCHES):
        # Giant k: the RAM cap scaled the stream below one full batch —
        # a coalescing measurement would be fiction (and the vmapped
        # batched program would materialize B giant EDSes).  The caller
        # emits no stream_b rows; batching giant squares is not a thing.
        return {}
    blocks = [_variant(ods, i, axis=0) for i in range(n)]
    warm_blocks = [_variant(ods, n + i, axis=0) for i in range(max(STREAM_BATCHES))]

    def feed(blist):
        for i, b in enumerate(blist):
            yield i, b

    # AOT-compile EVERY coalescing size 1..max up front: _coalesce is
    # opportunistic (it merges whatever happens to be queued), so a warm
    # STREAM alone cannot guarantee which batch executables get built —
    # and a multi-second jax compile landing inside the timed window
    # would corrupt a series bench_trend gates.  warmup() builds the
    # owned-input batched programs directly, race-free.
    from celestia_app_tpu.da.eds import warmup as _warmup

    _warmup(square_sizes=[k],
            batches=tuple(range(2, max(STREAM_BATCHES) + 1)))
    out: dict[int, float] = {}
    for batch in STREAM_BATCHES:
        depth = max(2, batch)
        # One warm stream per size on top of the AOT compiles: primes the
        # pipeline threads and any remaining first-dispatch cost.
        list(stream_blocks(feed(warm_blocks), k, depth=depth, batch=batch))
        t0 = time.perf_counter()
        for _tag, eds in stream_blocks(feed(blocks), k, depth=depth,
                                       batch=batch):
            eds.data_root()  # host sync per block, as a server would
        out[batch] = (time.perf_counter() - t0) / n
    return out


# --------------------------------------------------------------------------
# the mempool admission A/B (BENCH_MODE=mempool; no device, no jax)
# --------------------------------------------------------------------------


def _mempool_tx_sets(threads: int, per_thread: int):
    """One tenant per thread — whale (2 MiB txs), small tenants
    (512 KiB), one spammer (16 KiB) — with unique tx bytes, prebuilt so
    the timed window measures ADMISSION, not data generation.  sha256 of
    a big tx releases the GIL, so the work the old global lock
    serialized is exactly the work the sharded path runs concurrently;
    the sizes skew big because on a small-core host the GIL-serialized
    per-insert bookkeeping would otherwise drown the lock-contention
    difference the A/B exists to measure."""
    sets = []
    for t in range(threads):
        if t == 0:
            size = 4 * 1024 * 1024  # the whale
        elif t == threads - 1 and threads > 2:
            size = 32 * 1024  # the spammer: many tiny txs
        else:
            size = 1024 * 1024  # small tenants
        ns = f"{t:02x}"
        sets.append((ns, [
            (f"{ns}:{i}:".encode() + b"x" * size) for i in range(per_thread)
        ]))
    return sets


def _mempool_inserts_per_sec(shards: int, tx_sets) -> tuple[float, float]:
    """(inserts/sec, MB/s admitted) for one leg: every thread inserts its
    tenant's txs into ONE pool, wall-clocked from a shared barrier."""
    import threading as _threading

    from celestia_app_tpu.mempool import PriorityMempool

    pool = PriorityMempool(
        max_tx_bytes=1 << 30, max_pool_bytes=1 << 62, shards=shards
    )
    threads = len(tx_sets)
    barrier = _threading.Barrier(threads + 1)

    def worker(ns, txs):
        barrier.wait()
        for i, tx in enumerate(txs):
            pool.insert(tx, priority=i, height=0, ns=ns)

    workers = [
        _threading.Thread(target=worker, args=s, daemon=True)
        for s in tx_sets
    ]
    for w in workers:
        w.start()
    barrier.wait()
    t0 = time.perf_counter()
    for w in workers:
        w.join()
    wall = time.perf_counter() - t0
    n = len(pool)
    total_mb = pool.size_bytes() / 1e6
    return (n / wall if wall else 0.0), (total_mb / wall if wall else 0.0)


def _mempool_ab_rows(la: float, platform: str) -> list[dict]:
    """The sharded-vs-global admission A/B rows: identical prebuilt tx
    sets, the frozen global-lock rung first, then the sharded pool; the
    global row carries the measured speedup (the repair_grouped
    pattern: the baseline exists to be compared against)."""
    import sys as _sys

    threads = max(2, int(os.environ.get("BENCH_THREADS", "8") or 8))
    per_thread = max(8, int(os.environ.get("BENCH_MEMPOOL_TXS", "32")
                            or 32))
    iters = max(1, int(os.environ.get("BENCH_MEMPOOL_ITERS", "3") or 3))
    from celestia_app_tpu.mempool import mempool_shards

    stripes = mempool_shards() or 8  # sharded leg ignores a global pin
    # The timed window measures the admission path, not the telemetry
    # plane: span/table writes are identical GIL-serialized work on both
    # rungs and would only dilute the lock-contention difference under
    # measurement.  The GIL switch interval is pinned low for BOTH legs:
    # a hash-released thread otherwise waits out the default 5 ms slice
    # to reacquire, which is handoff latency, not admission cost.
    saved_trace = os.environ.get("CELESTIA_TRACE")
    saved_si = _sys.getswitchinterval()
    os.environ["CELESTIA_TRACE"] = "off"
    _sys.setswitchinterval(0.0005)
    try:
        tx_sets = _mempool_tx_sets(threads, per_thread)
        # One warm leg (fresh small pool) pays the import + allocator
        # warmup + page-faulting the prebuilt tx bytes.
        _mempool_inserts_per_sec(0, _mempool_tx_sets(threads, 8))
        # Alternate the rungs so host-load drift hits both; each rung
        # records its best iteration (the same max-collapse bench_trend
        # applies to duplicate rows within a round).
        g_best = s_best = (0.0, 0.0)
        for _ in range(iters):
            g = _mempool_inserts_per_sec(0, tx_sets)
            s = _mempool_inserts_per_sec(stripes, tx_sets)
            g_best = max(g_best, g)
            s_best = max(s_best, s)
        g_rate, g_mb = g_best
        s_rate, s_mb = s_best
    finally:
        _sys.setswitchinterval(saved_si)
        if saved_trace is None:
            os.environ.pop("CELESTIA_TRACE", None)
        else:
            os.environ["CELESTIA_TRACE"] = saved_trace
    common = {"threads": threads, "txs_per_thread": per_thread,
              "loadavg": round(la, 2), "platform": platform}
    return [
        {"stage": f"mempool_sharded@{threads}", "mode": "mempool_sharded",
         "k": threads, "shards": stripes,
         "inserts_per_s": round(s_rate, 1), "mb_per_s": round(s_mb, 3),
         **common},
        {"stage": f"mempool_global@{threads}", "mode": "mempool_global",
         "k": threads, "shards": 0,
         "inserts_per_s": round(g_rate, 1), "mb_per_s": round(g_mb, 3),
         "speedup_sharded_vs_global": (
             round(s_rate / g_rate, 3) if g_rate else None
         ),
         **common},
    ]


# --------------------------------------------------------------------------
# child: run stages, append a JSON line per completed stage
# --------------------------------------------------------------------------


def _stage_plan() -> list[dict]:
    only_k = os.environ.get("BENCH_K")
    only_mode = os.environ.get("BENCH_MODE")
    if only_k or only_mode:
        # BENCH_K accepts a comma-separated list so one round can carry a
        # multi-k sweep (the compute_sharded 1-vs-N recipe runs k=256 and
        # k=512 in one record); a single value stays a single stage.
        ks = [int(tok) for tok in (only_k or "128").replace(",", " ").split()]
        mode = only_mode or "extend"
        plan = [{"mode": mode, "k": k} for k in ks]
        if mode == "mempool":
            # The admission A/B needs no device and no host baseline —
            # and one stage regardless of any BENCH_K sweep.
            return [{"mode": "mempool", "k": 0}]
        if mode != "host" and not os.environ.get("BENCH_BASELINE_S"):
            plan.append({"mode": "host", "k": min(min(ks), 128)})
        return plan
    # Device rows run FIRST and the CPU-heavy host baseline LAST: round 2's
    # driver bench showed device timings collapse ~25x under concurrent
    # host load, so nothing CPU-bound may precede them.  parts@512 leads:
    # it doubles as the autotuner, so every later row (incl. the headline
    # compute rows) runs on the fastest measured RS/SHA lowerings.
    # compute@512 runs twice (early and end of the device block) as a
    # stability check.
    plan = [
        {"mode": "parts", "k": 512},
        {"mode": "compute", "k": 512},
        {"mode": "compute", "k": 256},
        {"mode": "compute", "k": 128},
        {"mode": "extend", "k": 128},
        {"mode": "extend", "k": 256},
        {"mode": "extend", "k": 512},
        {"mode": "repair", "k": 128},
        {"mode": "repair", "k": 256},
        {"mode": "stream", "k": 128},
        {"mode": "compute", "k": 512, "rerun": True},
        {"mode": "host", "k": 128},
    ]
    if os.environ.get("BENCH_BASELINE_S"):
        plan = [s for s in plan if s["mode"] != "host"]
    return plan


def _run_child() -> None:
    results_path = os.environ["BENCH_RESULTS_FILE"]
    deadline = float(os.environ["BENCH_DEADLINE"])

    device: dict = {}

    def emit(rec: dict) -> None:
        rec = {**rec, **device}
        with open(results_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
            os.fsync(f.fileno())
        # Same rows into the trace layer (a served node embedding the
        # bench exports them over GET /trace_tables; here they also feed
        # the parent's --metrics-out files).
        try:
            from celestia_app_tpu.trace import traced

            traced().write("bench_rows", **rec)
        except Exception:  # noqa: BLE001 — tracing never blocks a bench row
            pass

    import gc

    from celestia_app_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    device.update(platform=platform, device_kind=devs[0].device_kind,
                  n_devices=len(devs))
    if platform != "tpu":
        emit({"stage": "probe", "error": f"no TPU: JAX's first device is "
              f"{platform!r}; nothing measured"})
        sys.exit(3)
    emit({"stage": "probe"})

    def loadavg() -> float:
        try:
            return os.getloadavg()[0]
        except OSError:
            return 0.0

    def wait_for_quiet(max_wait: float = 90.0, threshold: float = 2.0) -> float:
        """Device timings collapse under concurrent host load (round-2
        lesson); wait briefly for the 1-min loadavg to settle, then proceed
        regardless — the load value is recorded with the row."""
        t_end = time.monotonic() + max_wait
        la = loadavg()
        while la > threshold and time.monotonic() < t_end:
            time.sleep(5)
            la = loadavg()
        return la

    for stage in _stage_plan():
        mode, k = stage["mode"], stage["k"]
        name = f"{mode}@{k}" + ("#2" if stage.get("rerun") else "")
        remaining = deadline - time.monotonic()
        # Rough floor: big squares need compile + transfer headroom.
        need = 120 if (k >= 256 or mode == "host") else 60
        if remaining < need:
            emit({"stage": name, "skipped": "budget",
                  "remaining_s": round(remaining, 1)})
            continue
        if k > 512:
            default_iters = "2"  # giant k: minutes per iteration
        elif k >= 256 and mode != "compute":
            default_iters = "3"
        else:
            default_iters = "5"
        iters = int(os.environ.get("BENCH_ITERS", default_iters))
        la = wait_for_quiet() if mode != "host" else loadavg()
        t_start = time.monotonic()
        try:
            if mode == "mempool":
                for row in _mempool_ab_rows(la, platform):
                    emit({**row,
                          "wall_s": round(time.monotonic() - t_start, 1)})
                gc.collect()
                continue
            ods = _random_ods(k)
            ods_mb = ods.nbytes / 1e6
            if mode == "parts":
                parts = _parts_seconds(ods, max(iters, 3))
                tuned = parts.pop("tuned", None)
                # Candidate-level faults (a challenger that failed to
                # compile or run) ride out as <label>_error notes next to
                # the rows that DID measure.
                part_errors = {
                    p: parts.pop(p)
                    for p in [q for q in parts if q.endswith("_error")]
                }
                emit({
                    "stage": name, "mode": mode, "k": k,
                    "parts_seconds": {p: round(s, 4) for p, s in parts.items()},
                    **part_errors,
                    "tuned": tuned,
                    "mb": ods_mb,
                    "wall_s": round(time.monotonic() - t_start, 1),
                    "loadavg": round(la, 2),
                })
                if tuned is not None:
                    # Autotune: every later stage (incl. the headline
                    # compute rows) rides the fastest measured lowerings
                    # and the winning fused-vs-staged pipeline.  Safe
                    # because nothing has built jit_pipeline yet — parts
                    # runs FIRST in the device block and uses fresh
                    # jax.jit wrappers, so the process-wide pipeline cache
                    # traces under this env.  An OPERATOR-set knob wins
                    # over the tuner: someone running the bench with
                    # CELESTIA_RS_FFT=on is measuring that path on
                    # purpose (parts saves/restores, so presence here
                    # means the operator set it).
                    target = _env_for_tuned(tuned)
                    for group in (
                        ("CELESTIA_RS_FFT", "CELESTIA_RS_FFT_MD",
                         "CELESTIA_RS_PALLAS", "CELESTIA_RS_XOR"),
                        ("CELESTIA_SHA_PALLAS", "CELESTIA_SHA_FUSED"),
                        ("CELESTIA_PIPE_FUSED",),
                    ):
                        if any(v in os.environ for v in group):
                            continue  # operator-set knob wins
                        _apply_env({v: target.get(v) for v in group})
                    # What later rows ACTUALLY run (operator knobs win
                    # over the tuner) — derived from the final env so the
                    # record can never contradict the headline rows.
                    emit({
                        "stage": "tuned-applied",
                        "applied": _applied_from_env(),
                    })
                gc.collect()
                continue
            if mode == "compute_sharded":
                # The multi-chip extend sweep: one row per ACTUAL shard
                # count over an identical plan (kernels/panel_sharded).
                # The panel seam must be on for the sharded rung to
                # engage; an operator-set height wins, otherwise the
                # recipe's 64-row default applies for the stage.
                saved_env = {
                    key: os.environ.get(key)
                    for key in ("CELESTIA_PIPE_PANEL",
                                "CELESTIA_EXTEND_SHARDS")
                }
                if not os.environ.get("CELESTIA_PIPE_PANEL"):
                    os.environ["CELESTIA_PIPE_PANEL"] = "64"
                measured: set[int] = set()
                try:
                    from celestia_app_tpu.kernels.panel_sharded import (
                        shards_for_k,
                    )

                    for want in _sharded_shard_counts():
                        # Dedupe on the POST-CLAMP actual count BEFORE
                        # burning the leg (the das-v2 sweep lesson): a
                        # clamped duplicate must cost a note, not a run.
                        os.environ["CELESTIA_EXTEND_SHARDS"] = (
                            str(want) if want > 1 else "0"
                        )
                        probe = shards_for_k(k) or 1
                        if probe in measured:
                            emit({"stage": f"compute_sharded{probe}@{k}",
                                  "skipped": "duplicate post-clamp shard "
                                             f"count (asked {want})"})
                            continue
                        t_leg = time.monotonic()
                        secs, actual = _compute_sharded_seconds(
                            ods, max(iters, 1), want
                        )
                        measured.add(actual)
                        emit({
                            "stage": f"compute_sharded{actual}@{k}",
                            "mode": f"compute_sharded{actual}", "k": k,
                            "shards": actual,
                            "seconds_per_block": secs, "mb": ods_mb,
                            "mb_per_s": round(ods_mb / secs, 3),
                            "wall_s": round(time.monotonic() - t_leg, 1),
                            "loadavg": round(la, 2),
                        })
                finally:
                    for key, val in saved_env.items():
                        if val is None:
                            os.environ.pop(key, None)
                        else:
                            os.environ[key] = val
                gc.collect()
                continue
            if mode == "host":
                secs = _host_seconds_per_block(ods)
                mb = ods_mb
            elif mode == "compute":
                # Giant squares take minutes per iteration on the CPU
                # fallback; 2 iterations still give a median while
                # letting BENCH_K=1024 finish inside a budget.  An
                # explicit BENCH_ITERS is the operator measuring
                # something ON PURPOSE (the README's one-iteration
                # peak-RSS recipe) and is never raised.
                floor = 1 if "BENCH_ITERS" in os.environ else (
                    5 if k <= 512 else 2)
                secs = _compute_seconds(ods, max(iters, floor))
                mb = ods_mb
            elif mode == "repair":
                secs = _repair_seconds(ods, iters)
                mb = 4 * ods_mb
            elif mode == "stream":
                secs = _stream_seconds(ods, iters)
                mb = ods_mb
            else:
                secs = _extend_seconds(ods, iters)
                mb = ods_mb
            emit({
                "stage": name, "mode": mode, "k": k,
                "seconds_per_block": secs, "mb": mb,
                "mb_per_s": round(mb / secs, 3),
                "wall_s": round(time.monotonic() - t_start, 1),
                "loadavg": round(la, 2),
            })
            if (mode == "repair" and k == 128
                    and "CELESTIA_REPAIR_SWEEP" not in os.environ):
                # The batched-repair A/B (ISSUE 10 acceptance bar): the
                # headline repair row runs the default batched sweep; this
                # companion row re-measures the frozen per-pattern-group
                # baseline so the speedup is a recorded fact, not a claim.
                # Operator-set CELESTIA_REPAIR_SWEEP means they are
                # measuring one path on purpose — no A/B then.
                t_b = time.monotonic()
                os.environ["CELESTIA_REPAIR_SWEEP"] = "grouped"
                try:
                    gsecs = _repair_seconds(ods, max(1, min(iters, 2)))
                finally:
                    os.environ.pop("CELESTIA_REPAIR_SWEEP", None)
                emit({
                    "stage": f"repair_grouped@{k}",
                    "mode": "repair_grouped", "k": k,
                    "seconds_per_block": gsecs, "mb": mb,
                    "mb_per_s": round(mb / gsecs, 3),
                    "speedup_batched_vs_grouped": round(gsecs / secs, 3),
                    "wall_s": round(time.monotonic() - t_b, 1),
                    "loadavg": round(la, 2),
                })
            if mode == "stream":
                # The continuous-batching rows ride the stream stage:
                # blocks/sec at batch ∈ STREAM_BATCHES coalesced same-k
                # squares per dispatch.  One row per size (mode
                # stream_b<N>), rate-shaped like every other row so
                # bench_trend gates them as a series; batch-1 is the
                # unbatched control the coalesced sizes are judged
                # against (batch-B seconds/block < batch-1 means B
                # squares in one dispatch cost less than B dispatches).
                t_b = time.monotonic()
                for batch, bsecs in _stream_batched_seconds(ods, iters).items():
                    emit({
                        "stage": f"stream_b{batch}@{k}",
                        "mode": f"stream_b{batch}", "k": k, "batch": batch,
                        "seconds_per_block": bsecs, "mb": ods_mb,
                        "mb_per_s": round(ods_mb / bsecs, 3),
                        "blocks_per_s": round(1.0 / bsecs, 3),
                        "wall_s": round(time.monotonic() - t_b, 1),
                        "loadavg": round(la, 2),
                    })
        except Exception as e:  # noqa: BLE001 — record and move on
            emit({"stage": name, "error": f"{type(e).__name__}: {e}"[:500]})
        gc.collect()  # release the stage's device buffers before the next
    emit({"stage": "done"})


# --------------------------------------------------------------------------
# parent: spawn the child, assemble the single JSON line
# --------------------------------------------------------------------------


def _run_measurement(env: dict, budget: float, results_path: str) -> None:
    env = dict(env)
    env["BENCH_RESULTS_FILE"] = results_path
    env["BENCH_DEADLINE"] = str(time.monotonic() + budget)
    env["_BENCH_CHILD"] = "1"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO_DIR, "bench.py")],
        cwd=_REPO_DIR, env=env,
        stdout=sys.stderr, stderr=sys.stderr,
    )
    try:
        proc.wait(timeout=budget + 120)
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def _read_results(path: str) -> list[dict]:
    recs = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        recs.append(json.loads(line))
                    except json.JSONDecodeError:
                        pass
    except FileNotFoundError:
        pass
    return recs


def _parse_metrics_out(argv: list[str]) -> str | None:
    """`--metrics-out <dir>` (or BENCH_METRICS_OUT): where the Prometheus
    textfile + JSONL tables land.  Hand-rolled so the no-flag invocation
    stays byte-compatible with every existing driver."""
    out = os.environ.get("BENCH_METRICS_OUT") or None
    args = list(argv)
    while "--metrics-out" in args:
        i = args.index("--metrics-out")
        if i + 1 >= len(args):
            print("bench: --metrics-out requires a directory", file=sys.stderr)
            break
        out = args[i + 1]
        del args[i : i + 2]
    return out


def _write_metrics_out(out_dir: str, recs: list[dict], summary: dict) -> None:
    """Write the bench's observability artifacts into `out_dir`:

      bench_metrics.prom  Prometheus textfile-collector exposition
                          (celestia_bench_* gauges/counters per row)
      bench_rows.jsonl    the tracer-table rows (one JSON object per
                          completed stage, the /trace_tables shape)

    Built from a PRIVATE registry/tracer: the files reflect this run only,
    never whatever else the process-wide registry accumulated.
    """
    from celestia_app_tpu.trace.metrics import Registry
    from celestia_app_tpu.trace.tracer import Tracer

    os.makedirs(out_dir, exist_ok=True)
    reg = Registry()
    # env_gated=False: these artifacts were explicitly requested; a
    # CELESTIA_TRACE=off perf run must not come back with empty files.
    tracer = Tracer(env_gated=False)
    rate = reg.gauge("celestia_bench_mb_per_s",
                     "per-stage ODS MB/s extended+DAH-hashed")
    secs = reg.gauge("celestia_bench_seconds_per_block",
                     "per-stage median seconds per block")
    errors = reg.counter("celestia_bench_errors_total",
                         "bench stages that raised")
    skipped = reg.counter("celestia_bench_stages_skipped_total",
                          "bench stages skipped (budget)")
    for rec in recs:
        if rec.get("stage") in ("probe", "plan", "done", "tuned-applied"):
            continue
        tracer.write("bench_rows", **rec)
        if "error" in rec:
            errors.inc(stage=str(rec.get("stage", "?")))
            continue
        if "skipped" in rec:
            skipped.inc(stage=str(rec.get("stage", "?")))
            continue
        # stage is part of the key: the compute@512 stability rerun ("#2")
        # shares {mode, k} with the primary and must not overwrite it.
        labels = {"mode": str(rec.get("mode", "?")), "k": str(rec.get("k", 0)),
                  "stage": str(rec.get("stage", "?"))}
        if "mb_per_s" in rec:
            rate.set(rec["mb_per_s"], **labels)
        if "seconds_per_block" in rec:
            secs.set(rec["seconds_per_block"], **labels)
    reg.gauge(
        "celestia_bench_headline_mb_per_s", "the summary line's headline rate"
    ).set(summary.get("value", 0))
    with open(os.path.join(out_dir, "bench_metrics.prom"), "w") as f:
        f.write(reg.render())
    with open(os.path.join(out_dir, "bench_rows.jsonl"), "w") as f:
        jsonl = tracer.export_jsonl("bench_rows")
        f.write(jsonl + "\n" if jsonl else "")


def main() -> None:
    if os.environ.get("_BENCH_CHILD") == "1":
        _run_child()
        return

    metrics_out = _parse_metrics_out(sys.argv[1:])

    budget = float(os.environ.get("BENCH_TOTAL_BUDGET", "1500"))
    fd, results_path = tempfile.mkstemp(prefix="bench_results_", suffix=".jsonl")
    os.close(fd)
    try:
        _run_measurement(dict(os.environ), budget, results_path)
        recs = _read_results(results_path)
    finally:
        try:
            os.unlink(results_path)
        except OSError:
            pass

    probe = next((r for r in recs if r.get("stage") == "probe"), None)
    if probe is None or probe.get("platform") != "tpu" or "error" in probe:
        why = (probe or {}).get("error", "the measurement child never "
                                "reported a device")
        print(f"bench: {why}", file=sys.stderr)
        sys.exit(1)
    platform = probe["platform"]
    dev_info = {k: probe[k] for k in ("platform", "device_kind", "n_devices")}
    errors = [r["error"] for r in recs if "error" in r]
    measured = [r for r in recs if "mb_per_s" in r or "parts_seconds" in r]

    device = [r for r in measured if r["mode"] not in ("host", "parts")]
    host = next((r for r in measured if r["mode"] == "host"), None)
    parts_only = next((r for r in measured if "parts_seconds" in r), None)

    if not device and not host:
        out = {
            "metric": "ODS MB/s erasure-extended + DAH-hashed per chip",
            "value": 0, "unit": "MB/s", "vs_baseline": 0,
            **dev_info,
        }
        if parts_only is not None:  # diagnostic BENCH_MODE=parts run
            out["parts"] = {
                "k": parts_only["k"], "seconds": parts_only["parts_seconds"],
                **({"tuned": parts_only["tuned"]} if parts_only.get("tuned") else {}),
            }
            if errors:  # rate stages may still have failed — say so
                out["errors"] = errors
        else:
            out["error"] = "; ".join(errors) or "no stage completed"
        if metrics_out:
            _write_metrics_out(metrics_out, recs, out)
        print(json.dumps(out))
        return

    # Headline: the largest compute row the plan actually ran (k=512, the
    # north-star size, in the default plan).  Its two
    # runs bracket the device block; their spread is the stability figure
    # (VERDICT r2: an unstable headline is nearly as bad as none).
    comp = [r for r in device if r["mode"] == "compute"]
    if comp:
        k_head = max(r["k"] for r in comp)
        cpair = [r for r in comp if r["k"] == k_head]
        primary = min(cpair, key=lambda r: r["seconds_per_block"])
    else:
        cpair = []
        primary = device[0] if device else host
    stability_pct = None
    if len(cpair) >= 2:
        rates = sorted(r["mb_per_s"] for r in cpair)
        stability_pct = round(100 * (rates[-1] - rates[0]) / rates[0], 1)

    base_env = os.environ.get("BENCH_BASELINE_S")
    if base_env:
        # BENCH_BASELINE_S is seconds per block at the PRIMARY stage's k.
        from celestia_app_tpu.constants import SHARE_SIZE

        host_rate = primary["k"] ** 2 * SHARE_SIZE / 1e6 / float(base_env)
    elif host:
        host_rate = host["mb_per_s"]
    else:
        host_rate = None
    out = {
        "metric": (f"ODS MB/s erasure-extended + DAH-hashed per chip "
                   f"(k={primary['k']}, {primary['mode']}, {platform})"),
        "value": primary["mb_per_s"],
        "unit": "MB/s",
        "vs_baseline": (round(primary["mb_per_s"] / host_rate, 3)
                        if host_rate else 0),
        **dev_info,
        "results": [
            {"mode": r["mode"], "k": r["k"], "mb_per_s": r["mb_per_s"],
             **dev_info,
             # The mempool A/B rows rate in inserts/sec + admitted MB/s
             # and have no per-block time; every device row keeps its
             # seconds_per_block.
             **({"seconds_per_block": round(r["seconds_per_block"], 4)}
                if "seconds_per_block" in r else {}),
             **({"inserts_per_s": r["inserts_per_s"]}
                if "inserts_per_s" in r else {}),
             **({"speedup_sharded_vs_global": r["speedup_sharded_vs_global"]}
                if "speedup_sharded_vs_global" in r else {}),
             **({"loadavg": r["loadavg"]} if "loadavg" in r else {}),
             **({"rerun": True} if r.get("stage", "").endswith("#2") else {})}
            for r in measured if "mb_per_s" in r  # parts rows lack rates
        ],
        "baseline_note": BASELINE_NOTE,
    }
    if parts_only is not None:
        applied = next(
            (r["applied"] for r in recs if r.get("stage") == "tuned-applied"),
            None,
        )
        out["parts"] = {
            "k": parts_only["k"], "seconds": parts_only["parts_seconds"],
            **({"tuned": parts_only["tuned"]} if parts_only.get("tuned") else {}),
            **({"applied": applied} if applied else {}),
        }
    if stability_pct is not None:
        out["stability_pct"] = stability_pct
    if errors:
        out["errors"] = errors
    if metrics_out:
        _write_metrics_out(metrics_out, recs, out)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
