"""Roofline-term extraction from a lowered/compiled SPMD module.

``collective_bytes`` is NOT in ``cost_analysis()`` — we parse the
post-partitioning HLO text and sum the operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
/ ragged-all-to-all.  The SPMD module is the *per-device* program, so the
sum is per-chip bytes on the wire; with the spec's convention
(collective term = Σ_global / (chips × link_bw)) the chips cancel:
term = per-chip bytes / link_bw.

Hardware constants come from ``PEAKS``, one entry per ``device_kind``;
the dry-run models a v5e pod, so its terms use the v5e entry.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    ici_bw: float         # bytes/s per ICI link
    hbm_bytes: int


# Published per-chip peaks keyed by jax ``Device.device_kind``.
# "TPU v5 lite" is TPU v5e — Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI per chip
# over 4 links (50 GB/s each).
V5E = "TPU v5 lite"
PEAKS: dict[str, ChipPeaks] = {
    V5E: ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                   hbm_bytes=16 * 1024 ** 3),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


_V5E_PEAKS = peaks(V5E)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

# e.g.  bf16[8,128,512]{2,1,0}
_TYPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _type_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind operand bytes summed over the module."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # match ' = <type> <op>(' and op-start variants
        m = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z-]+)(?:-start|-done)?\(",
                      stripped)
        if not m:
            continue
        op = m.group(1)
        base = op[:-6] if op.endswith("-start") else op
        if base not in _COLLECTIVES:
            continue
        if op.endswith("-done"):
            continue                      # counted at -start
        # operands are inside the call parens; types printed inline
        paren = stripped[stripped.index(op) + len(op):]
        total = 0
        for dt, dims in _TYPE_RE.findall(paren):
            total += _type_bytes(dt, dims)
        out[base] += total
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                # per-device HLO flops
    hbm_bytes: float            # per-device bytes accessed
    coll_bytes: float           # per-device collective operand bytes
    coll_breakdown: dict
    chips: int
    model_flops: float = 0.0    # 6·N·D (global)

    @property
    def compute_s(self) -> float:
        return self.flops / _V5E_PEAKS.flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / _V5E_PEAKS.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / _V5E_PEAKS.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs × chips): remat/redundancy waste."""
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time / bound time — the score we hillclimb."""
        if self.bound_s <= 0:
            return 0.0
        useful_s = self.model_flops / (self.chips * _V5E_PEAKS.flops)
        return useful_s / self.bound_s

    def to_dict(self) -> dict:
        return {
            "flops_per_dev": self.flops,
            "hbm_bytes_per_dev": self.hbm_bytes,
            "coll_bytes_per_dev": self.coll_bytes,
            "coll_breakdown": self.coll_breakdown,
            "chips": self.chips,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def analyze(compiled, chips: int, model_flops: float = 0.0,
            hlo_text: Optional[str] = None) -> Roofline:
    """Trip-count-aware totals via repro.launch.hlo_cost (XLA's own
    cost_analysis() visits while bodies once — see that module)."""
    from repro.launch import hlo_cost
    text = hlo_text if hlo_text is not None else compiled.as_text()
    cost = hlo_cost.analyze_text(text)
    return Roofline(flops=cost.flops, hbm_bytes=cost.bytes,
                    coll_bytes=cost.coll_bytes,
                    coll_breakdown={k: v for k, v in cost.coll.items()},
                    chips=chips, model_flops=model_flops)


def memory_summary(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        out[k] = int(getattr(ma, k, 0) or 0)
    out["peak_estimate_bytes"] = (out["argument_size_in_bytes"]
                                  + out["temp_size_in_bytes"]
                                  - out.get("alias_size_in_bytes", 0))
    out["fits_hbm"] = out["peak_estimate_bytes"] <= _V5E_PEAKS.hbm_bytes
    return out
