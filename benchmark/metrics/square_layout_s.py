"""Seconds of the square layout per window height: the proposer's
`square_build` and the validator's `square_construct` spans."""

from benchmark.spans import seconds_per_height


def read(ctx):
    return seconds_per_height(ctx, ("square_build", "square_construct"))
