"""Device milliseconds per execution of the fused extend + DAH program: the
union of its `XLA Modules` intervals in the trace over the executions the
host counted (`block_journal` rows of source `compute` in the window), as
`extend_dah_device_ms` computes it.  The program is matched by the stable
name its family gives it, `jit_extend_and_dah(<fingerprint>)`, whatever
the fingerprint; a program from before that name reads None."""

PREFIX = "jit_extend_and_dah("


def read(ctx):
    if ctx["kind"] != "propose" or not ctx["profile"]:
        return None
    from benchmark.profile import _union

    union, events = 0.0, 0
    for dev in ctx["profile"]["devices"]:
        iv = [x for name, d in dev["modules"].items() if name.startswith(PREFIX)
              for x in d]
        union += sum(hi - lo for lo, hi in _union(iv)) / 1e9
        events += len(iv)
    runs = sum(1 for r in ctx["spans"].get("block_journal", [])
               if r.get("source") == "compute" and r.get("k") == ctx["k"])
    if not events or not runs or union <= 0:
        return None
    return union / runs * 1e3
