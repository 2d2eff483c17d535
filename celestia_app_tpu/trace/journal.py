"""Block journal: one row per block through the device pipeline.

The observability spine of the PR-1 device pipeline: every block that
crosses `da/eds` (fused or staged), `parallel/pipeline.BlockPipeline`
(stream mode), or `parallel/sharded_eds` (multi-chip) records one
`block_journal` row — square size, pipeline mode, jit-cache hit/miss,
the stage timings its path measured (upload ms, dispatch ms, queue-stall
ms, drain latency), and the continuous-batching facts: `batch_size`
(squares coalesced into the row's dispatch; 1 = unbatched) on stream
rows, and `panels` (row panels the square
streamed through) on panel-mode rows ($CELESTIA_PIPE_PANEL) — read next
to the per-dispatch `celestia_hbm_peak_bytes{point,k,source}` refresh
below, the pair is the giant-square memory story per dispatch.  The batch-size distribution itself
lands on `celestia_pipeline_batch_size` (observed once per dispatch by
the pipeline, not once per row — a 4-square batch is ONE dispatch).  Rows are written from whichever thread ran the stage
(the uploader/dispatcher threads in stream mode) into the thread-safe
tracer tables and pulled node-side via GET /trace_tables — the
test/e2e/testnet/node.go:52-74 analog.

The same funnel feeds the Prometheus side: every `*_ms` timing lands on a
`celestia_block_<stage>_seconds` histogram with sub-millisecond buckets
(metrics.DEVICE_SECONDS_BUCKETS) and {source, k} labels, and each row
ticks the per-dispatch HBM high-water gauge plus the env-gated N-block
jax.profiler window (trace/profiler.py).

Nothing here syncs the device: all timings are host perf_counter deltas
around calls the pipeline already makes, and the HBM gauge reads allocator
stats only (None on CPU).
"""

from __future__ import annotations

TABLE = "block_journal"


def note_jit_build(program: str) -> None:
    """Count a jit program-cache build (the compile-counter the /metrics
    planes expose as celestia_jit_builds_total{program=...}).  Called from
    the lru_cache-missed builder bodies, so hits cost nothing."""
    from celestia_app_tpu.trace.metrics import registry

    registry().counter(
        "celestia_jit_builds_total",
        "jit pipeline wrapper builds (a miss traces + compiles on first dispatch)",
    ).inc(program=program)


def record(source: str, k: int, *, mode: str | None = None,
           compile: str | None = None, **fields) -> None:
    """Write one block-journal row + its Prometheus reflections.

    `source` names the path (compute | stream | sharded | warmup);
    `compile` is "hit"/"miss" against the jit wrapper cache.  Extra
    `fields` ending in `_ms` are stage timings: each is observed on
    `celestia_block_<stage>_seconds` with device-scale buckets; other
    fields (tags, depth, device counts) land only in the table row.
    """
    from celestia_app_tpu.trace.metrics import DEVICE_SECONDS_BUCKETS, registry
    from celestia_app_tpu.trace.tracer import traced

    # The profiler window and HBM gauge carry their OWN gates
    # ($CELESTIA_PROFILE_BLOCKS; stats availability) and must keep firing
    # when $CELESTIA_TRACE=off mutes the table/metric layer — profiling
    # with tracing muted is exactly the low-overhead measurement combo.
    from celestia_app_tpu.trace import profiler

    profiler.block_profiler().note_block()
    profiler.record_hbm_high_water(point=source, k=k)

    # The SLO engine ticks on the block funnel (rate-limited to
    # $CELESTIA_SLO_TICK_S): every block through the device pipeline is
    # a chance to notice the budget burning WITHOUT an external poller.
    # Outside the $CELESTIA_TRACE gate, like the profiler hooks — the
    # degraded/occupancy gauges it judges keep updating when tracing is
    # muted, so judgment must too.
    from celestia_app_tpu.trace.slo import engine

    engine().maybe_tick()

    tracer = traced()
    if not tracer._on():
        return
    # A dispatch running under a request/block trace stamps its row with
    # the trace_id — tying the device journal to the RPC-to-DAH span
    # tree — and the height riding the context baggage (the block trace
    # child minted in mempool reap), so the height timeline
    # (trace/timeline.py) can stitch the row without a join table.
    if "trace_id" not in fields or "height" not in fields:
        from celestia_app_tpu.trace.context import current_context

        ctx = current_context()
        if ctx is not None:
            fields.setdefault("trace_id", ctx.trace_id)
            height = ctx.baggage.get("height")
            if height is not None:
                fields.setdefault("height", height)
    tracer.write(TABLE, source=source, k=k, mode=mode, compile=compile,
                 **fields)
    reg = registry()
    if compile is not None:
        reg.counter(
            "celestia_pipeline_compile_total",
            "block dispatches by jit wrapper cache outcome",
        ).inc(result=compile, source=source)
    for name, value in fields.items():
        if not name.endswith("_ms") or value is None:
            continue
        reg.histogram(
            f"celestia_block_{name[:-3]}_seconds",
            f"per-block {name[:-3].replace('_', ' ')} time",
            buckets=DEVICE_SECONDS_BUCKETS,
        ).observe(value / 1e3, source=source, k=str(k))
