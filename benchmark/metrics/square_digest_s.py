"""Seconds of the `square_digest` spans per window height: the own-root
memo's SHA-256 over the square's shares, proposer and validator."""

from benchmark.spans import seconds_per_height


def read(ctx):
    return seconds_per_height(ctx, ("square_digest",))
