"""Mean seconds of the program's `square_pipeline` span (phase=prepare):
the proposer's extend + DAH dispatch, upload to roots on the host."""


def read(ctx):
    rows = [r for r in ctx["spans"].get("square_pipeline", [])
            if r.get("phase") == "prepare"]
    if ctx["kind"] != "propose" or not rows:
        return None
    return sum(r["duration_ms"] for r in rows) / len(rows) / 1e3
