"""Training step assembly + CLI driver.

``make_train_step`` wires model.loss → grads → (optional fp8
error-feedback compression) → AdamW into a single jit-able function whose
state is {"params", "opt"[, "err"]}.  The gradient-sync *structure*
(barrier vs MXDAG-planned layer-wise overlap) is selected by
``RunConfig.sync_mode`` inside the model (see repro/sync/overlap.py).

CLI:  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
          --steps 200 --batch 8 --seq 256 [--smoke] [--ckpt-dir DIR]
runs a real training with checkpoint/restart support: the full config on
a TPU, ``--smoke`` (the reduced config) on the CPU.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import math
import tempfile
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.base import ArchConfig, RunConfig, ShapeConfig
from repro.data import DataConfig, SyntheticLM
from repro.launch import compile_cache, sharding as shard_lib
from repro.launch.mesh import dp_axes, make_mesh
from repro.models import Model, attention
from repro.models import model as model_lib
from repro.optim import AdamW, AdamWConfig, compression, cosine_schedule


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """6·N·D (train) / 2·N·tokens (inference), N = active params."""
    n = cfg.param_counts()["active"]
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch        # decode: one token


def make_train_step(model: Model, optimizer: AdamW, run: RunConfig):
    grad_fn = jax.value_and_grad(
        lambda p, b: model.loss(p, b), has_aux=True)

    def compute_grads(params, batch):
        """Optionally gradient-accumulated over microbatches: peak
        activation memory scales 1/k while grads accumulate sharded."""
        k = run.microbatches
        if k <= 1:
            (_, metrics), grads = grad_fn(params, batch)
            return grads, metrics

        B = batch["tokens"].shape[0]
        mb = jax.tree.map(
            lambda x: x.reshape(k, B // k, *x.shape[1:]), batch)
        if model.mesh is not None:
            # PERF (hillclimb iter: internvl2#1): the reshape splits the
            # data-sharded batch dim; without a constraint GSPMD reshards
            # batch onto a 4-way slice of the mesh and REPLICATES
            # activations 4x across the rest (measured: per-layer
            # [B,S,d] all-gathers).  Pin: mb dim replicated, batch dim
            # sharded over dp.
            dp = model.dp_axes
            mb = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(model.mesh,
                                     P(None, dp,
                                       *([None] * (x.ndim - 2))))), mb)

        def body(gacc, mbatch):
            (_, metrics), g = grad_fn(params, mbatch)
            gacc = jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32), gacc, g)
            return gacc, metrics

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                          params)
        gsum, metrics_all = jax.lax.scan(body, g0, mb)
        grads = jax.tree.map(lambda g: g / k, gsum)
        metrics = jax.tree.map(lambda m: jnp.mean(m), metrics_all)
        return grads, metrics

    def train_step(state: dict, batch: dict):
        grads, metrics = compute_grads(state["params"], batch)

        new_state = dict(state)
        with jax.named_scope("optimizer"):
            if run.grad_compression:
                g8, scales, new_err = compression.compress_tree(
                    grads, state["err"])
                grads = compression.decompress_tree(g8, scales)
                new_state["err"] = new_err

            new_params, new_opt = optimizer.update(
                grads, state["opt"], state["params"])
        new_state["params"] = new_params
        new_state["opt"] = new_opt
        return new_state, metrics

    return train_step


def init_train_state(model: Model, optimizer: AdamW, run: RunConfig,
                     rng) -> dict:
    params = model.init(rng)
    state = {"params": params, "opt": optimizer.init(params)}
    if run.grad_compression:
        state["err"] = compression.init_error_state(params)
    return state


def state_shardings(state_shapes: dict, cfg: ArchConfig, run: RunConfig,
                    mesh) -> dict:
    out = {"params": shard_lib.param_shardings(
        state_shapes["params"], cfg, run, mesh)}
    out["opt"] = shard_lib.opt_state_shardings(
        state_shapes["opt"], state_shapes["params"], cfg, run, mesh)
    if "err" in state_shapes:
        out["err"] = shard_lib.param_shardings(
            state_shapes["err"], cfg, run, mesh)
    return out


@dataclasses.dataclass
class Trainer:
    """A jitted train step with its state placed on a mesh; batches go
    in under ``batch_shardings``."""
    step: Callable               # (state, batch) -> (state, metrics)
    init_state: Callable         # () -> state under ``state_shardings``
    state_shardings: Any
    state_shapes: Any
    batch_shardings: dict
    batch_shapes: dict


def build_trainer(cfg: ArchConfig, run: RunConfig, mesh, *, batch: int,
                  seq: int, steps: int, lr: float, seed: int = 0
                  ) -> Trainer:
    """Model and AdamW for ``cfg`` on ``mesh``.  The state is initialised
    directly into its shardings and the step's inputs and outputs keep
    them, so a multi-chip mesh never stages the state on one device."""
    model = Model(cfg, run, mesh=mesh, dp_axes=dp_axes(mesh))
    # warm up over the first tenth of the run (at most 20 steps), so a
    # short run reaches its peak rate
    opt = AdamW(AdamWConfig(
        lr=cosine_schedule(lr, warmup=min(20, steps // 10), total=steps)))

    def init():
        return init_train_state(model, opt, run, jax.random.PRNGKey(seed))

    state_shapes = jax.eval_shape(init)
    st_sh = state_shardings(state_shapes, cfg, run, mesh)
    # kept residuals raise the step's peak byte for byte (the backward
    # loop holds every layer's beside a copy of the current layer's), and
    # the step's other temporaries take about half of what the state
    # leaves (PERF.md §6): the layer scan keeps what fits in a third of
    # it, and a sixth stays spare
    stats = jax.local_devices()[0].memory_stats()
    if stats is not None:
        state_bytes = sum(
            math.prod(sh.shard_shape(x.shape)) * x.dtype.itemsize
            for x, sh in zip(jax.tree.leaves(state_shapes),
                             jax.tree.leaves(st_sh)))
        local = max(1, batch // (model.dp_size() * run.microbatches))
        need = collections.Counter()
        for seg in model.segments:
            need.update(model.tier_bytes(seg, local, seq))
        model.saved_tiers = model_lib.remat_saves(
            need, (stats["bytes_limit"] - state_bytes) / 3)
    batch_shapes = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    b_sh = shard_lib.batch_shardings(batch_shapes, mesh, run)
    step = jax.jit(make_train_step(model, opt, run),
                   in_shardings=(st_sh, b_sh),
                   out_shardings=(st_sh, NamedSharding(mesh, P())),
                   donate_argnums=0)
    return Trainer(step=step, init_state=jax.jit(init, out_shardings=st_sh),
                   state_shardings=st_sh,
                   state_shapes=state_shapes, batch_shardings=b_sh,
                   batch_shapes=batch_shapes)


# ----------------------------------------------------------------------
def main(argv: Optional[list[str]] = None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="mamba2-130m")
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced config")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--ckpt-dir", default=None,
                   help="resume from / checkpoint to this directory "
                        "(default: a fresh temporary one)")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--sync-mode", default="bucketed",
                   choices=["bucketed", "barrier"])
    p.add_argument("--mesh", default="1x1",
                   help="dataxmodel, e.g. 2x1")
    args = p.parse_args(argv)

    compile_cache.enable()
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get(args.arch)
    run = RunConfig(sync_mode=args.sync_mode, remat=True)
    traced = attention.DISPATCH.copy()
    remat_kept = model_lib.REMAT.copy()
    trainer = build_trainer(cfg, run, mesh, batch=args.batch, seq=args.seq,
                            steps=args.steps, lr=args.lr)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                  global_batch=args.batch),
                       sharding=trainer.batch_shardings["tokens"])

    from repro.runtime import LoopConfig, StepMonitor, run_training

    def on_step(step, metrics):
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {float(metrics['loss']):.4f}")

    monitor = StepMonitor()
    t0 = time.monotonic()
    with contextlib.ExitStack() as stack:
        ckpt_dir = args.ckpt_dir or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="repro_ckpt_"))
        summary = run_training(
            LoopConfig(total_steps=args.steps, ckpt_dir=ckpt_dir,
                       ckpt_every=args.ckpt_every),
            train_step=trainer.step,
            init_state=trainer.init_state,
            batch_at=data.batch_at,
            state_shardings=trainer.state_shardings,
            monitor=monitor,
            on_step=on_step)
    dt = time.monotonic() - t0
    kinds = collections.Counter(r.kind for r in monitor.reports)
    by_kind = ", ".join(f"{k} {n}" for k, n in sorted(kinds.items()))
    paths = attention.DISPATCH - traced
    by_path = ", ".join(f"{k} {n}" for k, n in sorted(paths.items()))
    kept = model_lib.REMAT - remat_kept
    by_tiers = ", ".join(f"{k} {n / 1e9:.2f} GB"
                         for k, n in sorted(kept.items()))
    print(f"done: {summary['final_step'] + 1} steps in {dt:.1f}s, "
          f"restarts={summary['restarts']}, "
          f"stragglers={len(monitor.reports)}"
          + (f" ({by_kind})" if by_kind else "")
          + (f", attention ({by_path})" if by_path else "")
          + (f", remat: {by_tiers}" if by_tiers else "")
          + f", loss {summary['loss_history'][0]:.3f} -> "
          f"{summary['loss_history'][-1]:.3f}")
    return summary


if __name__ == "__main__":
    main()
