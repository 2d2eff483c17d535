"""Thread CPU milliseconds of the `proof_gather`, `proof_assemble` and
`proof_encode` spans that ended in the window, per sample answered in it.
Beside their wall time it tells working from waiting (for the GIL or the
device): CPU ms times samples per second near 1000 ms/s is one core's
worth, the GIL's ceiling."""

from benchmark.spans import ms_per_sample


def read(ctx):
    return ms_per_sample(ctx, ("proof_gather", "proof_assemble", "proof_encode"),
                         field="cpu_ms")
