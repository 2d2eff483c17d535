"""Share of the device's idle time in the traced window that no leaf span
of the program covers on any host thread, in %.  Idle is the window less
the union of operation and program intervals, per device plane; a leaf
span is one of `benchmark.spans.LEAVES`, as the trace's host events name
them.  One reader for every kind of cell: `idle_unattributed.block` and
`idle_unattributed.das` fall back to this file.  A trace with no leaf span
(a program from before them) reads None."""

from benchmark.profile import _union
from benchmark.spans import LEAVES


def _minus(intervals, cut):
    """`intervals` less the union `cut` (both sorted and disjoint)."""
    out, j = [], 0
    for lo, hi in intervals:
        while j < len(cut) and cut[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(cut) and cut[k][0] < hi:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def unattributed(red: dict, window: tuple[int, int]) -> float | None:
    leaves = _union([(lo, hi) for lo, hi, name in red["host"] if name in LEAVES])
    if not red["devices"] or not leaves:
        return None
    idle_ns = uncovered_ns = 0
    for dev in red["devices"]:
        idle = _minus([window], dev["busy"])
        idle_ns += sum(hi - lo for lo, hi in idle)
        uncovered_ns += sum(hi - lo for lo, hi in _minus(idle, leaves))
    return uncovered_ns / idle_ns * 100.0 if idle_ns else None


def read(ctx):
    if not ctx["profile"] or not ctx["trace_seconds"]:
        return None
    return unattributed(ctx["profile"], (0, int(ctx["trace_seconds"] * 1e9)))
