"""Profiler window and the reduction of its `.xplane.pb` to numbers.

The reduction reads the trace with `jax.profiler.ProfileData` alone:

  * device planes are those named `/device:<PLATFORM>:<n>`; an operation
    is an event on the plane's `XLA Ops` line (every line of the plane
    where it has none);
  * busy = the union of operation and program (`XLA Modules`) intervals,
    per device plane that recorded anything, averaged over those planes;
    idle share = 1 - busy / window;
  * program time = the union of the intervals of the `XLA Modules` events
    of one module, named exactly (`jit_run(<fingerprint>)`), with their
    count; a caller divides it by the executions the host counted;
  * idle gaps are attributed to the host annotation (TraceAnnotation) that
    covers the gap's midpoint on the host planes, else to "host: other".

Tests feed it a small trace recorded on the CPU, where the host plane's
XLA client thread stands in for a device plane.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time


def find_xplane(root: str) -> str:
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return max(paths, key=os.path.getmtime)


class Window:
    """`with Window() as w:` traces the block; `w.path` is the xplane file,
    `w.seconds` the window's length on the host clock."""

    def __enter__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host annotations only, no per-call events
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self.seconds = time.perf_counter() - self.t0
        jax.profiler.stop_trace()
        self.path = find_xplane(self.dir)
        return False

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def reduce(path: str, device_prefix: str = "/device:", op_line: str = "XLA Ops",
           module_line: str = "XLA Modules", host_prefix: str = "/host:") -> dict:
    """Busy intervals, per-module device time and host annotations of a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        lines = list(plane.lines)
        # A device plane with no lines recorded nothing: v5e's trace carries
        # one beside the chip's own, and averaging it in halved busy time.
        if (plane.name.startswith(device_prefix) and lines
                and not plane.name.startswith("/device:CPU")):
            ops = [ln for ln in lines if ln.name.startswith(op_line)] or lines
            op_names = {ln.name for ln in ops}
            # A program's own interval counts as busy too: on v5e the ops
            # line leaves about half of the fused program's time uncovered.
            iv = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                  for ln in lines if ln.name in op_names or ln.name == module_line
                  for e in ln.events if e.duration_ns > 0]
            mods: dict[str, list[tuple[int, int]]] = {}
            op_time: dict[str, float] = {}
            for ln in lines:
                for e in ln.events:
                    if ln.name == module_line:
                        mods.setdefault(e.name, []).append(
                            (int(e.start_ns), int(e.start_ns + e.duration_ns)))
                    elif ln.name in op_names:
                        op_time[e.name] = op_time.get(e.name, 0.0) + e.duration_ns / 1e9
            devices.append({"name": plane.name, "busy": _union(iv),
                            "modules": mods, "ops": op_time,
                            "lines": [ln.name for ln in lines]})
        if plane.name.startswith(host_prefix):
            for ln in lines:
                for e in ln.events:
                    if e.duration_ns > 0:
                        host.append((int(e.start_ns), int(e.start_ns + e.duration_ns),
                                     e.name))
    return {"devices": devices, "host": host}


def busy_seconds(red: dict) -> float | None:
    """Union of operation time, averaged over the device planes."""
    devs = red["devices"]
    if not devs:
        return None
    return sum(sum(hi - lo for lo, hi in d["busy"]) for d in devs) / len(devs) / 1e9


def module_time(red: dict, name: str) -> tuple[float, int]:
    """(seconds of the union of its intervals, events) of the module named
    exactly `name`, over all devices."""
    total, count = 0.0, 0
    for d in red["devices"]:
        iv = d["modules"].get(name, [])
        total += sum(hi - lo for lo, hi in _union(iv)) / 1e9
        count += len(iv)
    return total, count


def covering(red: dict, t_ns: int) -> str:
    """The innermost host annotation covering instant `t_ns`."""
    cover = [h for h in red["host"] if h[0] <= t_ns < h[1] and not h[2].startswith("$")]
    return min(cover, key=lambda h: h[1] - h[0])[2] if cover else "host: other"


def breakdown(red: dict, top: int = 10, bin_ns: int = 1_000_000) -> dict:
    """The device operations that took most time, and the idle gaps summed
    by the innermost host event covering each gap's midpoint."""
    ops: dict[str, float] = {}
    for d in red["devices"]:
        for name, s in d["ops"].items():
            ops[name] = ops.get(name, 0.0) + s
    bins: dict[int, list] = {}  # time bin -> host events overlapping it
    for h in red["host"]:
        if not h[2].startswith("$"):
            for b in range(h[0] // bin_ns, h[1] // bin_ns + 1):
                bins.setdefault(b, []).append(h)
    by_label: dict[str, float] = {}
    for d in red["devices"][:1]:
        busy = d["busy"]
        for (_, a), (b, _) in zip(busy, busy[1:]):
            mid = (a + b) // 2
            covering = [h for h in bins.get(mid // bin_ns, ()) if h[0] <= mid < h[1]]
            label = min(covering, key=lambda h: h[1] - h[0])[2] if covering else "host: other"
            by_label[label] = by_label.get(label, 0.0) + (b - a) / 1e9
    return {
        "device_ops": sorted(([n, s] for n, s in ops.items()), key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s] for n, s in by_label.items()), key=lambda x: -x[1])[:top],
    }
