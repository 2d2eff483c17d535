"""Mean samples per batched dispatch: the `batch` of the program's
`proof_serve` tracer rows written in the window."""


def read(ctx):
    rows = ctx["spans"].get("proof_serve", [])
    if ctx["kind"] != "das" or not rows:
        return None
    return sum(r["batch"] for r in rows) / len(rows)
