"""Seconds of the `share_pack` spans per window height: the square's
share bytes and their join into one host array, proposer and validator."""

from benchmark.spans import seconds_per_height


def read(ctx):
    return seconds_per_height(ctx, ("share_pack",))
