#!/usr/bin/env python3
"""Readings that the limits of a cell's output comparison are set from.

    python3 benchmarks/tpu/calibrate.py --workload <cell> \
        --seeds 11,12,... [--control-seeds 11,12,13] [--seconds 2] \
        --out <dir>

In one process, for each seed: a run of the cell with a short window,
the program's numbers against the plain reference's; for each control
seed also the controls (the reference in float8 e4m3, put in the
program's place: ``fp8`` rounds the operands of every product,
``fp8act`` the residual stream too) and the reference with a planted
fault in the program's place: the loss and gradient over half of the
batch, and over the rows one of four data-parallel chips holds (the
exchange between chips left out).  One JSON line per seed goes to
stdout and to ``<out>/<cell>.jsonl``; a seed with controls carries the
raw readings (losses, leaf norms) of every side.  A state left unchanged reads 1 on
``change_gap`` by the measure, and needs no run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import bench
import spec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = bench.chip_devices(cell.chips)
    os.makedirs(args.out, exist_ok=True)
    ctrl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    batch = cell.mix["batch"]
    with open(os.path.join(args.out, f"{cell.name}.jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            controls = None
            if seed in ctrl_seeds:
                controls = {"fp8": {"precision": "fp8"},
                            "fp8act": {"precision": "fp8act"},
                            "half_batch": {"rows": batch // 2}}
                if batch % 4 == 0:
                    controls["one_of_four"] = {"rows": batch // 4}
            r = bench.run(cell, seed, args.seconds, False, devices,
                          controls=controls)
            line = json.dumps({"seed": seed, "checks": r["checks"],
                               "controls": r.get("controls"),
                               "raw": r.get("raw"),
                               "metrics": r["metrics"],
                               "memory_peak_bytes":
                                   r["device"]["memory_peak_bytes"]})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
