"""Mean seconds of the validator's App.process_proposal per height (the
benchmark's own timer)."""


def read(ctx):
    recs = ctx["records"]
    if ctx["kind"] != "propose" or not recs:
        return None
    return sum(r["process_s"] for r in recs) / len(recs)
