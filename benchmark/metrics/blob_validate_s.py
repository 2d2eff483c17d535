"""Seconds of the program's `blob_validate` spans per window height:
structural checks and share commitments of every blob tx, proposer and
validator."""

from benchmark.spans import seconds_per_height


def read(ctx):
    return seconds_per_height(ctx, ("blob_validate",))
