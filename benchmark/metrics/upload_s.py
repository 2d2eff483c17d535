"""Seconds of the `ods_upload` spans per window height: the ODS copied to
the device, proposer and validator."""

from benchmark.spans import seconds_per_height


def read(ctx):
    return seconds_per_height(ctx, ("ods_upload",))
