"""95th percentile (nearest rank) of round latency, due instant to last
proof, over every round due in the window; a round with a failed sample
counts as never answered (host clock)."""

import math

NEVER_MS = 1e9


def read(ctx):
    if ctx["kind"] != "das" or not ctx["rounds"]:
        return None
    lat = sorted(
        NEVER_MS if r["failed"] or r["done"] is None
        else (r["done"] - r["due_abs"]) * 1e3
        for r in ctx["rounds"]
    )
    return lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
