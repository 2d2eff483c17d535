"""Seconds of the program's `ante` spans per window height: the ante
handlers of PrepareProposal's filter and ProcessProposal's check, proposer
and validator."""

from benchmark.spans import seconds_per_height


def read(ctx):
    return seconds_per_height(ctx, ("ante",))
