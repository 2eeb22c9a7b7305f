"""Device milliseconds per step of the ops outside the layer loop that
belong to the forward or backward pass (op_name under jvp or
transpose): embedding, final norm, LM head and cross-entropy.  Averaged
over the cell's devices."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else t["class_ms"].get("head")
