"""Mean milliseconds from a worker taking a sample to its proof coming
back, over the samples answered inside the window (host clock): the serve
layer's own time per sample, without the queue in front of the workers."""


def read(ctx):
    if ctx["kind"] != "das" or not ctx["rounds"]:
        return None
    t = [done - began for r in ctx["rounds"] for *_, began, done in r["proofs"]
         if ctx["start"] <= done <= ctx["end"]]
    return sum(t) / len(t) * 1e3 if t else None
