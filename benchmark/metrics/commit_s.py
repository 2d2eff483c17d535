"""Seconds of the `finalize_block` and `commit` spans per window height,
both Apps."""

from benchmark.spans import seconds_per_height


def read(ctx):
    return seconds_per_height(ctx, ("finalize_block", "commit"))
