#!/usr/bin/env python3
"""Bring-up smoke on a TPU: train full-width mamba2-130m through the
planned training step.

    python3 chip_smoke.py [--steps 20] [--seed 0] [--out DIR]
    python3 chip_smoke.py --four-chips

Default (one chip): prints ``plan_sync``'s sync-mode decision for one
chip, then trains the published mamba2-130m config (24 layers, d_model
768, vocab 50280; random weights and data from ``--seed``) at batch 8 x
seq 2048 under that mode, through ``repro.launch.train.build_trainer`` and
``repro.runtime.run_training`` as ``python -m repro.launch.train`` does.

``--four-chips``: the same config on a 2x2 data x model mesh, in barrier
and in bucketed sync mode on identical batches, and nothing else.  The
two runs' losses must agree step by step, and the parameter and
optimizer shards must sit on 4 distinct devices.

Earlier lines give the compile time, the median step time after warm-up
(each step ended when its outputs are ready), loss first -> last and the
device's ``peak_bytes_in_use``; ``--out`` receives the same as
``result.json`` (and holds the run's checkpoints while it runs).  A
non-finite or non-falling loss, or a step count short of the one asked
for, fails the run.  The last line of stdout is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``; without a TPU
the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

BATCH, SEQ, LR = 8, 2048, 1e-3
# The synthetic stream draws its tokens from the first DATA_VOCAB ids, as
# real text spends most of its tokens on a small part of the vocabulary.
# Learning that takes a few steps, so the loss falls well clear of the
# batch-to-batch noise; over all 50280 ids the stream's unigram is
# uniform and 20 steps move the loss by less than that noise.  The
# logits still span the full vocabulary.
DATA_VOCAB = 4096
WARMUP_STEPS = 2          # steps left out of the median step time
LOSS_RTOL = LOSS_ATOL = 2e-2   # bf16 tolerance, as in tests/test_kernels.py


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _train(cfg, run, mesh, *, steps: int, seed: int, out_dir: str,
           tag: str) -> dict:
    """One training run of ``steps`` steps; returns its readings, with
    where the initial state landed: the fewest devices holding shards of
    any state leaf, and whether any leaf is split rather than
    replicated."""
    import jax
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.train import build_trainer
    from repro.runtime import LoopConfig, run_training

    trainer = build_trainer(cfg, run, mesh, batch=BATCH, seq=SEQ,
                            steps=steps, lr=LR, seed=seed)
    data = SyntheticLM(DataConfig(vocab_size=DATA_VOCAB, seq_len=SEQ,
                                  global_batch=BATCH, seed=seed),
                       sharding=trainer.batch_shardings["tokens"])
    t0 = time.monotonic()
    step = trainer.step.lower(trainer.state_shapes,
                              trainer.batch_shapes).compile()
    compile_s = time.monotonic() - t0

    placement = {}

    def init_state():
        state = trainer.init_state()
        leaves = jax.tree.leaves(state)
        placement["state_devices"] = min(
            len({s.device for s in x.addressable_shards}) for x in leaves)
        placement["state_split"] = any(
            not x.sharding.is_fully_replicated for x in leaves)
        return state

    # run_training resumes from the newest checkpoint it finds, so each
    # run starts from an empty directory
    ckpt_dir = os.path.join(out_dir, f"ckpt_{tag}")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    try:
        summary = run_training(
            LoopConfig(total_steps=steps, ckpt_dir=ckpt_dir,
                       ckpt_every=steps),
            train_step=step, init_state=init_state,
            batch_at=data.batch_at,
            state_shardings=trainer.state_shardings)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    losses = summary["loss_history"]
    _check(summary["restarts"] == 0 and summary["final_step"] == steps - 1
           and len(losses) == steps,
           f"{tag}: ran {len(losses)} of {steps} steps "
           f"(restarts={summary['restarts']})")
    _check(all(math.isfinite(x) for x in losses),
           f"{tag}: non-finite loss {losses}")
    _check(losses[-1] < losses[0],
           f"{tag}: loss did not fall ({losses[0]} -> {losses[-1]})")
    peak = max(d.memory_stats()["peak_bytes_in_use"]
               for d in mesh.devices.flat)
    rec = {"sync_mode": run.sync_mode, "mesh": dict(mesh.shape),
           "compile_s": compile_s,
           "median_step_s": statistics.median(
               summary["step_times"][WARMUP_STEPS:]),
           "loss_first": losses[0], "loss_last": losses[-1],
           "losses": losses, "peak_bytes_in_use": peak,
           "tokens_per_step": BATCH * SEQ, **placement}
    print(f"[{tag}] sync={run.sync_mode} mesh={dict(mesh.shape)} "
          f"compile {compile_s:.2f}s  median step {rec['median_step_s']:.4f}s "
          f"(steps {WARMUP_STEPS}..{steps - 1})  loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}  peak_bytes_in_use {peak}  every state leaf "
          f"on {placement['state_devices']} device(s), split leaves: "
          f"{placement['state_split']}", flush=True)
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="2x2 mesh: barrier vs bucketed, nothing else")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    args = p.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform}); "
              f"nothing measured", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, jax found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro import configs
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.launch import compile_cache
    from repro.launch.mesh import make_mesh
    from repro.sync.plan import plan_sync

    compile_cache.enable()
    os.makedirs(args.out, exist_ok=True)
    cfg = configs.get("mamba2-130m")
    shape = ShapeConfig("chip_smoke", SEQ, BATCH, "train")
    result = {"device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)},
              "config": cfg.name, "batch": BATCH, "seq": SEQ,
              "data_vocab": DATA_VOCAB, "lr": LR,
              "steps": args.steps, "seed": args.seed}
    try:
        if not args.four_chips:
            plan = plan_sync(cfg, shape, chips=1, tp=1)
            print(f"plan_sync(mamba2-130m, {BATCH}x{SEQ}, chips=1): "
                  f"mode={plan.mode} predicted barrier "
                  f"{plan.predicted_barrier:.6f}s bucketed "
                  f"{plan.predicted_bucketed:.6f}s", flush=True)
            mesh = make_mesh((1, 1), ("data", "model"),
                             devices=devices[:1])
            run = RunConfig(sync_mode=plan.mode, remat=True)
            result["plan_mode"] = plan.mode
            result["runs"] = [_train(cfg, run, mesh, steps=args.steps,
                                     seed=args.seed, out_dir=args.out,
                                     tag="1chip")]
        else:
            mesh = make_mesh((2, 2), ("data", "model"),
                             devices=devices[:4])
            runs = {}
            for mode in ("barrier", "bucketed"):
                run = RunConfig(sync_mode=mode, remat=True)
                rec = runs[mode] = _train(cfg, run, mesh, steps=args.steps,
                                          seed=args.seed, out_dir=args.out,
                                          tag=f"2x2-{mode}")
                _check(rec["state_devices"] == 4 and rec["state_split"],
                       f"{mode}: state not spread over 4 devices")
            worst = 0.0
            for i, (a, b) in enumerate(zip(runs["barrier"]["losses"],
                                           runs["bucketed"]["losses"])):
                diff = abs(a - b)
                worst = max(worst, diff)
                _check(diff <= LOSS_ATOL + LOSS_RTOL * abs(a),
                       f"step {i}: barrier loss {a} vs bucketed {b}")
            print(f"[2x2] barrier vs bucketed: max |loss diff| {worst:.6f} "
                  f"over {args.steps} steps", flush=True)
            result["runs"] = list(runs.values())
            result["max_loss_diff"] = worst
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        with open(os.path.join(args.out, "result.json"), "w") as f:
            json.dump(result, f, indent=1)

    print(json.dumps({"ok": True, "device": result["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
