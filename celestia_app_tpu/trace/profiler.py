"""JAX profiler + HBM accounting hooks (env-gated, off the hot path).

Two device-side instruments the journal funnel drives per block:

  * an N-block `jax.profiler.start_trace`/`stop_trace` window:
    $CELESTIA_PROFILE_BLOCKS=N arms it; the trace starts on the first
    journaled block and stops after N, writing the TensorBoard-loadable
    trace under $CELESTIA_PROFILE_DIR (default /tmp/celestia_jax_trace).
    One window per process — profiling is a measurement run, not a
    steady-state cost;
  * a memory high-water gauge:
    celestia_hbm_peak_bytes{point=...,k=...,source=...}, refreshed per
    journaled dispatch.  `source="device"` is the allocator's
    peak_bytes_in_use from `device.memory_stats()`; backends that keep
    no stats (this image's CPU) fall back to `source="rss"` — the
    process peak RSS from resource.getrusage — so the giant-square
    memory-high-water claims stay MEASURABLE off-chip.  The label keeps
    the two sources from ever being compared as one series: RSS is a
    process-lifetime peak (it never goes down, and it includes the host
    heap), device stats are the allocator's own.

This is the instrument for the ROADMAP TODO "measure whether donation
moves the k=512 HBM high-water mark enough to deepen the stream pipeline
past depth 2" and for the panel-vs-materializing residency comparison
(README "Giant squares"): run the bench once per seam setting, diff the
gauge (or, on CPU, one process per setting — RSS peaks are per-process).
"""

from __future__ import annotations

import os
import threading


def profile_blocks_target() -> int:
    """$CELESTIA_PROFILE_BLOCKS: how many journaled blocks the jax
    profiler window spans (0 = disabled)."""
    try:
        return int(os.environ.get("CELESTIA_PROFILE_BLOCKS", "0") or "0")
    except ValueError:
        return 0


def profile_dir() -> str:
    return os.environ.get("CELESTIA_PROFILE_DIR", "/tmp/celestia_jax_trace")


class BlockProfiler:
    """One env-gated profiler window per process, advanced per block."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active = False
        self._remaining = 0
        self._done = False

    def note_block(self) -> None:
        target = profile_blocks_target()
        if target <= 0 or self._done:
            return
        with self._lock:
            if self._done:
                return
            if not self._active:
                if not self._start(target):
                    return
            self._remaining -= 1
            if self._remaining <= 0:
                self._stop()

    def _start(self, target: int) -> bool:
        from celestia_app_tpu.trace.tracer import traced

        logdir = profile_dir()
        try:
            import jax

            os.makedirs(logdir, exist_ok=True)
            jax.profiler.start_trace(logdir)
        except Exception as e:  # noqa: BLE001 — profiling must never take
            # down the block path; record the failure once and disarm.
            self._done = True
            traced().write("profiler", event="start_failed",
                           error=f"{type(e).__name__}: {e}"[:200])
            return False
        self._active = True
        self._remaining = target
        traced().write("profiler", event="started", blocks=target,
                       logdir=logdir)
        return True

    def _stop(self) -> None:
        from celestia_app_tpu.trace.tracer import traced

        try:
            import jax

            jax.profiler.stop_trace()
            traced().write("profiler", event="stopped", logdir=profile_dir())
        except Exception as e:  # noqa: BLE001
            traced().write("profiler", event="stop_failed",
                           error=f"{type(e).__name__}: {e}"[:200])
        self._active = False
        self._done = True  # one window per process


_PROFILER = BlockProfiler()


def block_profiler() -> BlockProfiler:
    return _PROFILER


def hbm_high_water(device=None) -> int | None:
    """Peak device-memory bytes from the allocator, or None when the
    backend keeps no stats (CPU).  A stats read, never a device sync."""
    try:
        import jax

        device = device or jax.devices()[0]
        stats = device.memory_stats()
    except Exception:  # noqa: BLE001 — absent API / uninitialized backend
        return None
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
    return int(peak) if peak else None


def rss_high_water() -> int | None:
    """Process peak RSS in bytes (resource.getrusage ru_maxrss) — the
    CPU-fallback memory high-water.  A lifetime peak, never a per-phase
    one: comparing two pipeline configurations needs one process each."""
    try:
        import resource

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    except Exception:  # noqa: BLE001 — absent module/odd platform: no sample
        return None
    if not peak:
        return None
    import sys

    # Linux reports KiB; macOS bytes.
    return int(peak) * (1 if sys.platform == "darwin" else 1024)


def record_hbm_high_water(point: str = "dispatch",
                          k: int | None = None) -> int | None:
    """Refresh celestia_hbm_peak_bytes{point,k,source}; returns the peak
    bytes.  Device allocator stats when the
    backend keeps them (source="device"), else the process peak RSS
    (source="rss") so the high-water stays measurable on CPU images;
    None only when neither source can answer."""
    peak, source = hbm_high_water(), "device"
    if peak is None:
        peak, source = rss_high_water(), "rss"
    if peak is None:
        return None
    from celestia_app_tpu.trace.metrics import registry

    labels = {"point": point, "source": source}
    if k is not None:
        labels["k"] = str(k)
    registry().gauge(
        "celestia_hbm_peak_bytes",
        "memory high-water mark (device allocator peak_bytes_in_use, or "
        "process peak RSS on stat-less backends — see the source label)",
    ).set(peak, **labels)
    return peak
