"""Reduction of a profiler trace of training steps to per-layer numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, and the HLO text
of the compiled step (``compiled.as_text()``), which names for every
instruction its computation, opcode and ``op_name`` stack.

- Device ops are the events of each TPU plane's "XLA Ops" line.  An op is
  classed by its instruction in the step's HLO:
  ``collective`` (all-reduce, all-gather, reduce-scatter,
  collective-permute, all-to-all, with their -start/-done halves);
  ``blocks`` (inside a ``while`` body: the layer scan, forward or
  transpose, with every loop nested in it); ``head`` (outside every
  loop, with ``jvp(`` or ``transpose(`` in its ``op_name``: embedding,
  final norm, LM head, cross-entropy); ``optimizer`` (the rest of the
  step); ``other`` (ops of other programs, or not found in the HLO).
- Busy time is the union of a device's op intervals inside the traced
  window; idle share is 1 - busy / window.
- Exposed collective time is, per device, the part of the union of its
  collective ops that no other op of that device overlaps.
- Idle time of the first device is split by the host span
  (``TraceAnnotation``) running meanwhile, ``no_span`` where none ran,
  and summed by span.

The traced window runs from the start of the first host span named
``window_start`` to the end of the last named ``window_end``; its steps
are the ``window_start`` spans that begin inside it.
"""
from __future__ import annotations

import collections
import re
from typing import Iterable, Optional

COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all",
                      "ragged-all-to-all")
CONTAINER_OPCODES = ("while", "conditional", "call")

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
# a TPU op event is named by its HLO instruction: "%fusion.12 = f32[..] ..."
_EVENT_RE = re.compile(r"^%?([\w.\-]+)\s*=")
_CALLEE_RE = re.compile(r"\b(?:body|condition|calls|to_apply|"
                        r"true_computation|false_computation)=%?([\w.\-]+)"
                        r"|\bbranch_computations=\{([^}]*)\}")


def hlo_index(hlo_text: str) -> dict[str, dict]:
    """instruction name -> {"opcode", "op_name", "in_loop"}."""
    comp = None
    instrs: dict[str, dict] = {}
    calls: dict[str, set] = collections.defaultdict(set)
    bodies: set[str] = set()
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m and not line[:1].isspace():        # instructions are indented
            comp = m.group(2)
            continue
        m = _INSTR_RE.match(line)
        if not m or comp is None:
            continue
        name, opcode = m.group(1), m.group(2)
        op = _OPNAME_RE.search(line)
        instrs[name] = {"opcode": opcode, "op_name": op.group(1) if op
                        else "", "comp": comp}
        for g in _CALLEE_RE.finditer(line):
            for callee in re.split(r"[,\s%]+", g.group(1) or g.group(2)):
                if callee:
                    calls[comp].add(callee)
                    if opcode == "while":
                        bodies.add(callee)
    in_loop: set[str] = set()
    todo = list(bodies)
    while todo:
        c = todo.pop()
        if c in in_loop:
            continue
        in_loop.add(c)
        todo.extend(calls.get(c, ()))
    for v in instrs.values():
        v["in_loop"] = v.pop("comp") in in_loop
    return instrs


def op_class(name: str, index: dict[str, dict]) -> str:
    info = index.get(name)
    if info is None:
        return "other"
    base = info["opcode"].removesuffix("-start").removesuffix("-done")
    if base in COLLECTIVE_OPCODES:
        return "collective"
    if info["opcode"] in CONTAINER_OPCODES:
        return "container"
    if info["in_loop"]:
        return "blocks"
    if "jvp(" in info["op_name"] or "transpose(" in info["op_name"]:
        return "head"
    return "optimizer"


# ---------------------------------------------------------------------------
def union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(ivs: Iterable) -> float:
    return sum(e - s for s, e in ivs)


def clip(ivs: Iterable, lo: float, hi: float) -> list[list[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in ivs
            if min(e, hi) > max(s, lo)]


def subtract(a: list, b: list) -> list[list[float]]:
    """Parts of the sorted disjoint intervals ``a`` not covered by the
    sorted disjoint intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# ---------------------------------------------------------------------------
def load_events(path: str):
    """(device ops by device, host spans) from an ``.xplane.pb``; times
    in seconds on the trace's clock.  Device ops: [(name, start, end)];
    host spans: [(name, start, end)] of every host thread."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" \
                not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    m = _EVENT_RE.match(ev.name)
                    ops.append((m.group(1) if m else ev.name, s,
                                s + ev.duration_ns * 1e-9))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return devices, host


def reduce(devices: dict[str, list], host: list, index: dict[str, dict],
           *, window_start: str, window_end: str,
           spans: tuple[str, ...]) -> Optional[dict]:
    """The per-layer numbers of a traced window, or None where the trace
    holds no device op in it.  The window runs from the first
    ``window_start`` span to the end of the last ``window_end`` span; its
    steps are the ``window_start`` spans that begin inside it."""
    starts = [s for n, s, _ in host if n == window_start]
    ends = [e for n, _, e in host if n == window_end]
    if not starts or not ends or not devices:
        return None
    lo, hi = min(starts), max(ends)
    steps = sum(1 for s in starts if s < hi)
    if hi <= lo or steps == 0:
        return None
    window = hi - lo
    host_spans = sorted((s, e, n) for n, s, e in host if n in spans)
    per_dev = []
    op_time: dict[str, float] = collections.defaultdict(float)
    for dev, ops in sorted(devices.items()):
        ops = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        cls_time: dict[str, float] = collections.defaultdict(float)
        coll, comp = [], []
        for n, s, e in ops:
            c = op_class(n, index)
            if c == "container":
                continue
            s, e = max(s, lo), min(e, hi)
            cls_time[c] += e - s
            op_time[f"{n} [{c}]"] += e - s
            (coll if c == "collective" else comp).append((s, e))
        busy = union(coll + comp)
        exposed = subtract(union(coll), union(comp))
        per_dev.append({"busy": length(busy), "cls": cls_time,
                        "exposed": length(exposed), "n_coll": len(coll),
                        "gaps": subtract([[lo, hi]], busy)})
    if not any(d["busy"] > 0 for d in per_dev):
        return None
    n = len(per_dev)
    classes = sorted({c for d in per_dev for c in d["cls"]})
    # what the host was doing while device 0 sat idle, summed by span
    gap_by: dict[str, float] = collections.defaultdict(float)
    spans_ivs = union((s, e) for s, e, _ in host_spans)
    for s, e in per_dev[0]["gaps"]:
        for hs, he, hn in host_spans:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                gap_by[hn] += ov
        gap_by["no_span"] += length(subtract([[s, e]], spans_ivs))
    return {
        "devices": n,
        "steps": steps,
        "window_s": window,
        "busy_s": sum(d["busy"] for d in per_dev) / n,
        "idle_pct": max(100.0 * (1 - d["busy"] / window) for d in per_dev),
        "class_ms": {c: 1e3 * sum(d["cls"].get(c, 0.0) for d in per_dev)
                     / n / steps for c in classes},
        "exposed_collective_ms": (
            1e3 * sum(d["exposed"] for d in per_dev) / n / steps
            if any(d["n_coll"] for d in per_dev) else None),
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda t: -t[1])[:10],
        "idle_gaps": sorted(([k, v] for k, v in gap_by.items() if v > 0),
                            key=lambda t: -t[1])[:10],
    }
