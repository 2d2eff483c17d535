"""Wall milliseconds of the sampler's `proof_gather` spans (index plan and
node and share gathers) that ended in the window, per sample answered in
it."""

from benchmark.spans import ms_per_sample


def read(ctx):
    return ms_per_sample(ctx, ("proof_gather",))
