"""1 - (union of device op intervals) / traced window, in percent; the
largest over the cell's devices."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else t["idle_pct"]
