"""Mean lateness of the load generator: actual issue minus due instant, per
round (host clock).  A large value means a starved generator, not a slow
server."""


def read(ctx):
    rounds = [r for r in ctx["rounds"] or [] if r.get("issued") is not None]
    if ctx["kind"] != "das" or not rounds:
        return None
    return sum(r["issued"] - r["due_abs"] for r in rounds) / len(rounds) * 1e3
