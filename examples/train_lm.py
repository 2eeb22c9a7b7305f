"""End-to-end training driver: a real LM trained for a few hundred steps
on the synthetic pipeline, with checkpoint/restart fault tolerance and
the MXDAG-planned gradient sync.

The model is the deepseek-7b architecture scaled to ~20M params (CPU
container; the full configs are exercised by the dry-run).  Loss descends
from ~8.3 to <1 on the learnable synthetic stream; a simulated failure at
step 120 exercises the restart path.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 240]
"""
import argparse
import contextlib
import dataclasses
import sys
import tempfile
import time

sys.path.insert(0, "src")

import jax

from repro import configs
from repro.configs.base import RunConfig
from repro.data import DataConfig, SyntheticLM
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.train import init_train_state, make_train_step
from repro.models import Model
from repro.optim import AdamW, AdamWConfig, cosine_schedule
from repro.runtime import LoopConfig, StepMonitor, run_training
from repro.sync.plan import plan_sync
from repro.configs.base import SHAPES


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=240)
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint directory (default: a fresh temporary "
                        "one, so every run starts from step 0)")
    args = p.parse_args()

    # deepseek-7b family at ~20M params
    cfg = dataclasses.replace(
        configs.get("deepseek-7b"), name="deepseek-20m",
        n_layers=4, d_model=256, n_heads=8, n_kv_heads=8, head_dim=32,
        d_ff=1024, vocab_size=4096)
    n = cfg.param_counts()["total"]
    print(f"arch: {cfg.name} ({n/1e6:.1f}M params)")

    # the MXDAG plan for this arch at PRODUCTION scale (what the paper's
    # scheduler decides for the real 256-chip run)
    plan = plan_sync(configs.get("deepseek-7b"), SHAPES["train_4k"])
    print(f"MXDAG sync plan @256 chips: mode={plan.mode}, "
          f"predicted {plan.predicted_barrier:.3f}s -> "
          f"{plan.predicted_bucketed:.3f}s "
          f"({(plan.predicted_speedup-1)*100:.1f}% step-time win), "
          f"order={plan.order[:4]}...")

    compile_cache.enable()
    mesh = make_mesh((1, 1), ("data", "model"))
    run = RunConfig(sync_mode=plan.mode, remat=True, microbatches=1)
    model = Model(cfg, run, mesh=mesh)
    opt = AdamW(AdamWConfig(
        lr=cosine_schedule(1e-3, warmup=20, total=args.steps),
        weight_decay=0.01))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=256,
                                  global_batch=8))

    step_fn = jax.jit(make_train_step(model, opt, run), donate_argnums=0)
    monitor = StepMonitor()

    def on_step(step, metrics):
        if step % 20 == 0 or step == args.steps - 1:
            print(f"  step {step:4d}  loss {float(metrics['loss']):.4f}")

    t0 = time.monotonic()
    with contextlib.ExitStack() as stack:
        ckpt_dir = args.ckpt_dir or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="repro_example_ckpt_"))
        summary = run_training(
            LoopConfig(total_steps=args.steps, ckpt_dir=ckpt_dir,
                       ckpt_every=60, fail_at_step=120),  # injected failure!
            train_step=step_fn,
            init_state=lambda: init_train_state(model, opt, run,
                                                jax.random.PRNGKey(0)),
            batch_at=data.batch_at,
            monitor=monitor,
            on_step=on_step)
    dt = time.monotonic() - t0
    first, last = summary["loss_history"][0], summary["loss_history"][-1]
    print(f"\ndone: {args.steps} steps in {dt:.0f}s, "
          f"restarts={summary['restarts']} (failure injected at step 120, "
          f"resumed from checkpoint), loss {first:.3f} -> {last:.3f}")
    assert summary["restarts"] == 1 and last < first


if __name__ == "__main__":
    main()
