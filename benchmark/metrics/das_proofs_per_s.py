"""Proofs returned inside the window over the window's seconds (host clock)."""


def read(ctx):
    if ctx["kind"] != "das" or not ctx["rounds"]:
        return None
    n = sum(1 for r in ctx["rounds"] for *_, t in r["proofs"]
            if ctx["start"] <= t <= ctx["end"])
    return n / (ctx["end"] - ctx["start"])
