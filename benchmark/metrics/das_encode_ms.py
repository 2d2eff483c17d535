"""Wall milliseconds of the `proof_encode` spans (JSON form of the proof and
the coverage tick) that ended in the window, per sample answered in it."""

from benchmark.spans import ms_per_sample


def read(ctx):
    return ms_per_sample(ctx, ("proof_encode",))
