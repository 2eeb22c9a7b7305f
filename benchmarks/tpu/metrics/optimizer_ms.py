"""Device milliseconds per step of the step's ops outside the layer loop
and outside jvp/transpose, collectives excluded: the AdamW update with
its clipping norm.  Averaged over the cell's devices."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else t["class_ms"].get("optimizer")
