"""Device milliseconds per execution of the fused extend + DAH program: the
union of its `XLA Modules` intervals in the trace, over the executions the
host counted (the program's `block_journal` rows of source `compute` in
the window).  The module is matched by its exact name, fingerprint
included, as PERF.md records it for each k: the program is jitted from a
function named `run` (kernels/fused.py), and so are the staged, DAH-only,
repair and panel programs.  Another lowering has another fingerprint, and
then this reader finds nothing."""

PROGRAMS = {512: "jit_run(9481681487966387175)"}


def read(ctx):
    name = PROGRAMS.get(ctx["k"])
    if ctx["kind"] != "propose" or not ctx["profile"] or name is None:
        return None
    from benchmark.profile import module_time

    union, events = module_time(ctx["profile"], name)
    runs = sum(1 for r in ctx["spans"].get("block_journal", [])
               if r.get("source") == "compute" and r.get("k") == ctx["k"])
    if not events or not runs or union <= 0:
        return None
    return union / runs * 1e3
