"""Share of the traced window in which no operation ran on the device:
1 - union of operation and program intervals / window, in %.  One reader
for every kind of cell: `device_idle.block` and `device_idle.das` fall
back to this file."""


def read(ctx):
    if not ctx["profile"] or not ctx["trace_seconds"]:
        return None
    from benchmark.profile import busy_seconds

    busy = busy_seconds(ctx["profile"])
    if busy is None:
        return None
    return (1.0 - busy / ctx["trace_seconds"]) * 100.0
