"""Seconds of the `extend_dispatch` spans per window height: the host
call that enqueues the extend + DAH program (its compile on a cache
miss), proposer and validator."""

from benchmark.spans import seconds_per_height


def read(ctx):
    return seconds_per_height(ctx, ("extend_dispatch",))
