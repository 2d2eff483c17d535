"""The program's own span rows, read for the per-layer metrics.

Every span of the program writes a row to the tracer table of its name
(`celestia_app_tpu.trace.context.trace_span`), with `duration_ms`,
`start_ns`/`end_ns` on the host clock the profiler stamps its events
with, `cpu_ms` (thread CPU time) while a profiler records, as in a
traced run, and the `height` and `phase` of the block it worked for.  The readers take only the window's rows:

  * propose cells: the rows of the window's heights.  Nothing proposes
    after the window, so those are the last `len(records)` heights the
    proposer's `prepare_proposal` rows name;
  * DAS cells: the rows that ended inside the window, whose bounds are
    moved from the benchmark's clock (`time.perf_counter`) to the rows'
    (`time.time_ns`) by the offset between the two read now.

A program that writes no such rows (one from before the spans) reads
None, never 0.
"""

from __future__ import annotations

import time

# The spans that do work and open no span inside: what the idle-gap
# attribution counts as covered.  A parent (prepare_proposal,
# square_pipeline) covers its children's gaps too, so it is left out.
LEAVES = (
    "ante", "blob_validate", "square_build", "square_construct",
    "square_digest", "share_pack", "ods_upload", "extend_dispatch",
    "roots_wait", "finalize_block", "commit",
    "proof_gather", "proof_assemble", "proof_encode",
)


def table(name: str) -> list[dict]:
    """The rows of one span table that carry the span clock."""
    try:
        from celestia_app_tpu.trace.tracer import traced
    except ImportError:
        return []
    return [r for r in traced().table(name) if "start_ns" in r]


def window_heights(ctx) -> list[int]:
    n = len(ctx["records"] or [])
    heights = sorted({r["height"] for r in table("prepare_proposal")
                      if isinstance(r.get("height"), int)})
    return heights[-n:] if n else []


def seconds_per_height(ctx, names: tuple[str, ...]) -> float | None:
    """Seconds the named spans took per window height, summed over the
    proposer and the validator."""
    if ctx["kind"] != "propose":
        return None
    heights = set(window_heights(ctx))
    rows = [r for name in names for r in table(name)
            if r.get("height") in heights]
    if not heights or not rows:
        return None
    return sum(r["duration_ms"] for r in rows) / 1e3 / len(heights)


def window_ns(ctx) -> tuple[int, int]:
    offset = time.time_ns() - time.perf_counter_ns()
    return (int(ctx["start"] * 1e9) + offset, int(ctx["end"] * 1e9) + offset)


def answered(ctx) -> int:
    """Samples answered inside the window (das_proofs_per_s' count)."""
    return sum(1 for r in ctx["rounds"] or [] for *_, t in r["proofs"]
               if ctx["start"] <= t <= ctx["end"])


def ms_per_sample(ctx, names: tuple[str, ...], field: str = "duration_ms"
                  ) -> float | None:
    """`field` of the named spans that ended in the window, summed, per
    sample answered in it."""
    if ctx["kind"] != "das":
        return None
    lo, hi = window_ns(ctx)
    rows = [r for name in names for r in table(name)
            if lo <= r["end_ns"] <= hi and field in r]
    n = answered(ctx)
    if not rows or not n:
        return None
    return sum(r[field] for r in rows) / n
