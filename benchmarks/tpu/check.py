"""The comparison that decides ``correct`` for a training cell.

Numbers, each a gap between the program's first training steps and the
plain reference's:

- ``loss_gap``: over the compared steps, the largest
  |loss_program - loss_reference| / |loss_reference|;
  ``first_loss_gap`` the same at the first step alone, before any
  update.
- ``grad_gap``: the first gradient as the optimizer gets it (its first
  moment after one step, over 1 - b1), by the worst leaf: the gap
  between the two sides' norms of the leaf over the larger of the
  reference's norm of that leaf and of the median leaf.
  ``median_grad_gap`` is the median leaf's gap, measured the same way.
- ``change_gap``: each leaf's change over the compared steps, by the
  worst leaf, measured the same way; ``median_change_gap`` the median
  leaf's.  Leaves whose reference gradient is under a thousandth of the
  median leaf's move under Adam by round-off alone and are left out.

A cell's limits file names the numbers that are compared; the others
are printed, not judged.
"""
from __future__ import annotations

import statistics

SMALL_GRADIENT = 1e-3


def _leaf_gaps(prog: dict, ref: dict, keep) -> list[float]:
    names = [n for n in ref if keep(n)]
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: program {sorted(prog)} "
                         f"reference {sorted(ref)}")
    med = statistics.median(ref[n] for n in names)
    return [abs(prog[n] - ref[n]) / max(ref[n], med) if
            max(ref[n], med) > 0 else 0.0 for n in names]


def gaps(prog: dict, ref: dict) -> dict:
    gr = ref["grad_norm"]
    med = statistics.median(gr.values())
    moved = lambda n: gr[n] >= SMALL_GRADIENT * med  # noqa: E731
    loss = [abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])]
    grad = _leaf_gaps(prog["grad_norm"], gr, lambda n: True)
    change = _leaf_gaps(prog["change_norm"], ref["change_norm"], moved)
    return {"loss_gap": max(loss), "first_loss_gap": loss[0],
            "grad_gap": max(grad),
            "median_grad_gap": statistics.median(grad),
            "change_gap": max(change),
            "median_change_gap": statistics.median(change)}


def judge(numbers: dict, limits: dict | None) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers the limits
    name: every one at or under its limit.  A cell without limits is
    never correct."""
    named = {} if limits is None else limits["limits"]
    out = {name: {"value": numbers[name], "limit": lim}
           for name, lim in named.items()}
    ok = bool(out) and all(c["value"] == c["value"]
                           and c["value"] <= c["limit"] for c in out.values())
    return ok, out
