"""Seconds of the `roots_wait` spans per window height: the first host
read of the roots, which waits for the device program, proposer and
validator."""

from benchmark.spans import seconds_per_height


def read(ctx):
    return seconds_per_height(ctx, ("roots_wait",))
