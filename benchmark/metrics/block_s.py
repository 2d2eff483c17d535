"""Window seconds over the heights completed in it (host clock)."""


def read(ctx):
    if ctx["kind"] != "propose" or not ctx["records"]:
        return None
    return (ctx["end"] - ctx["start"]) / len(ctx["records"])
