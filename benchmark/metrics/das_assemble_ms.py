"""Wall milliseconds of the sampler's `proof_assemble` spans that ended in
the window, per sample answered in it."""

from benchmark.spans import ms_per_sample


def read(ctx):
    return ms_per_sample(ctx, ("proof_assemble",))
