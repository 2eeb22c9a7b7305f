"""SyncPlan: MXDAG-driven gradient-sync planning (the paper → the mesh).

``step_mxdag`` builds the Fig. 6 MXDAG for one training step of an
assigned arch at production scale: BP/FP compute MXTasks per layer (sized
from the roofline constants) and push/pull network MXTasks for each
layer's gradient reduce-scatter + param all-gather (sized from grad bytes
over ICI bandwidth).  ``plan_sync`` then schedules it with the Principle-1
scheduler and compares against the barrier (coflow-like all-at-the-end)
schedule — choosing ``bucketed`` (per-layer collectives inside the
backward loop, overlappable) only when the MXDAG analysis predicts a win,
exactly the paper's "pipelines applied only when they shrink execution
time".  The realized JAX mechanism is repro/sync/overlap.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.configs.base import ArchConfig, RunConfig, ShapeConfig
from repro.core import MXDAGScheduler, simulate
from repro.core.builders import ddl
from repro.launch.hlo_analysis import V5E, peaks


@dataclasses.dataclass
class SyncPlan:
    mode: str                      # "bucketed" | "barrier"
    order: list[str]               # push priority order (layer names)
    predicted_bucketed: float      # MXDAG-scheduled makespan (s)
    predicted_barrier: float       # single-barrier makespan (s)
    mxdag_size: int

    @property
    def predicted_speedup(self) -> float:
        return self.predicted_barrier / max(self.predicted_bucketed, 1e-12)


def _per_layer_times(cfg: ArchConfig, shape: ShapeConfig, chips: int,
                     tp: int) -> tuple[float, float, float]:
    """(fp_s, bp_s, sync_s) per layer per step at the assigned scale."""
    n_layer = cfg.param_counts()["active"] / max(cfg.n_layers, 1)
    tokens = shape.global_batch * shape.seq_len
    dp = max(chips // tp, 1)
    chip = peaks(V5E)
    fp = 2.0 * n_layer * tokens / (chips * chip.flops)
    bp = 2.0 * fp
    # grad RS + param AG: 2 × layer grad bytes (bf16) across dp over ICI
    layer_bytes = (cfg.param_counts()["total"] / max(cfg.n_layers, 1)) \
        * 2.0 / tp
    sync = 2.0 * layer_bytes * (dp - 1) / dp / chip.ici_bw
    return fp, bp, sync


def step_mxdag(cfg: ArchConfig, shape: ShapeConfig, *, chips: int = 256,
               tp: int = 16, n_layers: Optional[int] = None,
               unit_frac: Optional[float] = None):
    """Fig. 6 MXDAG for one step (push=grad RS, pull=param AG).
    ``unit_frac`` makes tasks pipelineable (chunked collectives)."""
    L = n_layers or cfg.n_layers
    fp, bp, sync = _per_layer_times(cfg, shape, chips, tp)
    return ddl(L, bp=bp, fp=fp, push=sync / 2, pull=sync / 2,
               unit_frac=unit_frac)


def plan_sync(cfg: ArchConfig, shape: ShapeConfig, *, chips: int = 256,
              tp: int = 16, run: Optional[RunConfig] = None) -> SyncPlan:
    L = cfg.n_layers
    g = step_mxdag(cfg, shape, chips=chips, tp=tp)
    sched = MXDAGScheduler(try_pipelining=False).schedule(g)
    bucketed = sched.simulate().makespan

    # barrier baseline: all pushes/pulls grouped as one coflow each —
    # gradient sync happens strictly after the full backward
    fp, bp, sync = _per_layer_times(cfg, shape, chips, tp)
    gb = ddl(1, bp=bp * L, fp=fp * L, push=sync * L / 2, pull=sync * L / 2)
    barrier = simulate(gb).makespan

    prio = {k: v for k, v in sched.priorities.items()
            if k.startswith("push")}
    order = sorted(prio, key=lambda k: prio[k])
    mode = "bucketed" if bucketed < barrier - 1e-12 else "barrier"
    return SyncPlan(mode=mode, order=order,
                    predicted_bucketed=bucketed,
                    predicted_barrier=barrier,
                    mxdag_size=len(g))
