"""Least time of one extend + DAH over its measured device time, in %, as
`extend_dah_roofline` computes it, over `extend_dah_program_ms`: the
fused program found by its stable name, whatever its fingerprint.  Least
time = the bytes the algorithm must move (benchmark/counts.py) over the
HBM peak of the device kind (benchmark/peaks.json): a bytes-only bound."""


def read(ctx):
    if not ctx["peaks"]:
        return None
    from benchmark.counts import extend_dah_bytes
    from benchmark.run import read_metric

    ms = read_metric("extend_dah_program_ms", ctx)
    if ms is None:
        return None
    least = extend_dah_bytes(ctx["k"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return least / (ms / 1e3) * 100.0
