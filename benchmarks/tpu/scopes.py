#!/usr/bin/env python3
"""Split of a traced training window by the program's named scopes and
host spans, beside ``trace_reduce.reduce``.

    python3 benchmarks/tpu/scopes.py <dir>

reads a trace kept by ``bench.py --trace 1 --keep-trace <dir>`` and
prints the ``trace:`` classes, a ``trace scopes:`` line and the traced
steps' periods; the last line is all of it as one JSON object.

Input, as ``trace_reduce.reduce`` takes it: device ops by device, host
spans and the step's HLO index; the same window and steps.

- The scope path of a device op is the segments of its HLO ``op_name``
  that name a scope of ``repro.models.SCOPES``, in order, each read bare
  or inside ``jvp(x)`` / ``transpose(jvp(x))``.  ``scope_ms[s]`` is the
  device time per step of the ops whose path holds ``s`` (so
  ``attention`` includes ``attention_core``), averaged over devices;
  ``scope_pass_ms[s]`` splits it by pass: ``recompute``
  (``rematted_computation`` in the ``op_name``), else ``backward``
  (``transpose(``), else ``forward`` (``jvp(``), else ``other``.
  ``blocks_unscoped`` is the time per step of ``blocks``-class ops
  under no block-level scope (``BLOCK_SCOPES``).
- ``program_idle_ms`` splits the first device's idle time per step by
  the innermost program span running meanwhile: a ``run_training.*``
  span, ``train`` (inside the step annotation, under no
  ``run_training.*`` span) or ``no_span``.  Each idle instant counts
  once, so the split sums to the idle time; the harness's ``bench.*``
  spans play no part.
"""
from __future__ import annotations

import collections
import json
import re
import statistics
import sys
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import trace_reduce as trace_lib  # noqa: E402
from repro.models import SCOPES  # noqa: E402

BLOCK_SCOPES = ("attention", "ssm", "mlp", "moe")
STEP_SPAN = "train"
LOOP_PREFIX = "run_training."

_WRAPPED_RE = re.compile(r"\w+\((.*)\)")


def scope_path(op_name: str) -> tuple[str, ...]:
    out = []
    for seg in op_name.split("/"):
        while (m := _WRAPPED_RE.fullmatch(seg)):
            seg = m.group(1)
        if seg in SCOPES:
            out.append(seg)
    return tuple(out)


def pass_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "other"


def innermost_split(gaps: list, spans: list) -> dict[str, float]:
    """Seconds of the intervals ``gaps`` under each innermost span of
    ``spans`` [(name, start, end)], properly nested as one thread's are;
    ``no_span`` where none runs."""
    out: dict[str, float] = collections.defaultdict(float)
    for s, e in gaps:
        over = [t for t in spans if t[1] < e and t[2] > s]
        cuts = sorted({s, e} | {x for _, a, b in over for x in (a, b)
                                if s < x < e})
        for a, b in zip(cuts, cuts[1:]):
            live = [t for t in over if t[1] <= a and t[2] >= b]
            # the innermost of nested spans starts last and ends first
            name = max(live, key=lambda t: (t[1], -t[2]))[0] if live \
                else "no_span"
            out[name] += b - a
    return dict(out)


def reduce_scopes(devices: dict[str, list], host: list,
                  index: dict[str, dict], *, window_start: str,
                  window_end: str) -> Optional[dict]:
    """The scope and program-span splits of the window that
    ``trace_reduce.reduce`` reads, or None where it holds no device op."""
    starts = [s for n, s, _ in host if n == window_start]
    ends = [e for n, _, e in host if n == window_end]
    if not starts or not ends or not devices:
        return None
    lo, hi = min(starts), max(ends)
    steps = sum(1 for s in starts if s < hi)
    if hi <= lo or steps == 0:
        return None
    scope_t: dict[str, float] = collections.defaultdict(float)
    pass_t: dict[tuple, float] = collections.defaultdict(float)
    unscoped = 0.0
    busy0 = None
    for dev, ops in sorted(devices.items()):
        busy = []
        for n, s, e in ops:
            if e <= lo or s >= hi:
                continue
            c = trace_lib.op_class(n, index)
            if c == "container":
                continue
            s, e = max(s, lo), min(e, hi)
            busy.append((s, e))
            op_name = index[n]["op_name"] if n in index else ""
            path = scope_path(op_name)
            for scope in set(path):
                scope_t[scope] += e - s
                pass_t[scope, pass_of(op_name)] += e - s
            if c == "blocks" and not set(path) & set(BLOCK_SCOPES):
                unscoped += e - s
        if busy0 is None:
            busy0 = busy
    if not busy0:
        return None
    per_step = 1e3 / len(devices) / steps
    gaps = trace_lib.subtract([[lo, hi]], trace_lib.union(busy0))
    program = [(n, s, e) for n, s, e in host
               if n == STEP_SPAN or n.startswith(LOOP_PREFIX)]
    idle = innermost_split(gaps, program)
    passes: dict[str, dict] = collections.defaultdict(dict)
    for (scope, p), t in sorted(pass_t.items()):
        passes[scope][p] = t * per_step
    return {
        "steps": steps,
        "scope_ms": {k: v * per_step for k, v in sorted(scope_t.items())},
        "scope_pass_ms": dict(passes),
        "blocks_unscoped": unscoped * per_step,
        "program_idle_ms": {k: v * 1e3 / steps
                            for k, v in sorted(idle.items())},
    }


def step_periods(host: list, window_start: str) -> list[float]:
    """Seconds from each ``window_start`` span's start to the next's."""
    starts = sorted(s for n, s, _ in host if n == window_start)
    return [b - a for a, b in zip(starts, starts[1:])]


def main(argv=None) -> int:
    import bench
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    kept = Path(argv[0])
    (xplane,) = kept.rglob("*.xplane.pb")
    index = trace_lib.hlo_index((kept / "step.hlo.txt").read_text())
    devs, host = trace_lib.load_events(str(xplane))
    kw = {"window_start": bench.SPANS[0], "window_end": bench.SPANS[2]}
    base = trace_lib.reduce(devs, host, index, spans=bench.SPANS, **kw)
    scoped = reduce_scopes(devs, host, index, **kw)
    periods = step_periods(host, bench.SPANS[0])
    print("trace: device ms per step by class "
          + json.dumps(base and base["class_ms"]))
    print("trace scopes: " + json.dumps(scoped))
    print("traced step periods s: " + json.dumps(periods)
          + (f"; median {statistics.median(periods):.6f}" if periods
             else ""))
    print(json.dumps({"trace": base, "scopes": scoped,
                      "step_periods_s": periods}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
