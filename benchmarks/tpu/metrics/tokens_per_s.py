"""Tokens trained in the window over the window's wall time, summed over
the cell's chips.  The window runs from the end of the last warm-up step
to the end of the last step, both read when the step's outputs are
ready."""


def read(ctx):
    w = ctx["window"]
    return w["tokens"] / w["seconds"]
