"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this:
  1. builds the production mesh (16×16 single-pod or 2×16×16 multi-pod),
  2. constructs the model + ShapeDtypeStruct inputs (zero allocation),
  3. jits the right step (train_step / forward / serve_step) with the
     sharding rules of launch/sharding.py, ``.lower()``s and
     ``.compile()``s it,
  4. prints memory_analysis() (proves it fits) and cost_analysis(),
  5. extracts the three roofline terms (launch/hlo_analysis.py) and
     appends the record to benchmarks/results/dryrun.json (incremental —
     reruns skip completed cells unless --force).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
      --shape train_4k --multi-pod both
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import os
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.base import ArchConfig, RunConfig, SHAPES, \
    applicable_shapes
from repro.launch import compile_cache, hlo_analysis, sharding as shard_lib
from repro.launch.mesh import dp_axes, make_production_mesh, n_chips
from repro.launch.specs import decode_specs, input_specs
from repro.launch.train import (init_train_state, make_train_step,
                                model_flops, state_shardings)
from repro.launch.serve import make_serve_step
from repro.models import Model
from repro.optim import AdamW, AdamWConfig

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "results")


def default_run(cfg: ArchConfig, overrides: Optional[dict] = None
                ) -> RunConfig:
    n = cfg.param_counts()["total"]
    small = n < 1e9
    fsdp = n > 5e9
    # §Perf dsv3 iter 2: with FSDP every microbatch re-gathers params, so
    # fewer/larger microbatches win (AG traffic halves; stash still fits)
    base = RunConfig(fsdp=fsdp, opt_8bit=n > 2.5e10, remat=True,
                     batch_axes="all" if small else "dp",
                     microbatches=1 if small else (2 if fsdp else 4))
    if overrides:
        import dataclasses
        base = dataclasses.replace(base, **overrides)
    return base


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               run_overrides: Optional[dict] = None,
               verbose: bool = True) -> dict:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    run = default_run(cfg, run_overrides)
    if (run_overrides is None or "seq_shard" not in run_overrides) \
            and run.batch_axes == "all" \
            and shape.global_batch % mesh.devices.size != 0:
        # §Perf mamba2 iter 4: when the batch cannot fill the mesh, shard
        # the sequence over the otherwise-idle "model" axis (57x on
        # mamba2 prefill); when it can, plain batch sharding wins.
        import dataclasses
        run = dataclasses.replace(run, seq_shard=True)
    model_dp = (tuple(mesh.axis_names) if run.batch_axes == "all"
                else dp_axes(mesh))
    model = Model(cfg, run, mesh=mesh, dp_axes=model_dp)
    chips = n_chips(mesh)
    mf = model_flops(cfg, shape)

    t0 = time.monotonic()
    with mesh:
        if shape.kind == "train":
            opt = AdamW(AdamWConfig(state_8bit=run.opt_8bit))
            state_shapes = jax.eval_shape(
                lambda: init_train_state(model, opt, run,
                                         jax.random.PRNGKey(0)))
            st_sh = state_shardings(state_shapes, cfg, run, mesh)
            batch = input_specs(cfg, shape)
            b_sh = shard_lib.batch_shardings(batch, mesh, run)
            step = make_train_step(model, opt, run)
            lowered = jax.jit(step, in_shardings=(st_sh, b_sh),
                              donate_argnums=0).lower(state_shapes, batch)
        elif shape.kind == "prefill":
            params_shapes = jax.eval_shape(model.init,
                                           jax.random.PRNGKey(0))
            p_sh = shard_lib.param_shardings(params_shapes, cfg, run, mesh)
            batch = input_specs(cfg, shape)
            b_sh = shard_lib.batch_shardings(batch, mesh, run)
            lowered = jax.jit(model.forward,
                              in_shardings=(p_sh, b_sh)
                              ).lower(params_shapes, batch)
        else:                                    # decode
            params_shapes = jax.eval_shape(model.init,
                                           jax.random.PRNGKey(0))
            p_sh = shard_lib.param_shardings(params_shapes, cfg, run, mesh)
            tokens, cache, index = decode_specs(model, cfg, shape)
            c_sh = shard_lib.cache_shardings(cache, cfg, mesh)
            t_sh = shard_lib.batch_shardings(tokens, mesh, run)
            i_sh = NamedSharding(mesh, P())
            step = make_serve_step(model)
            lowered = jax.jit(step,
                              in_shardings=(p_sh, c_sh, t_sh, i_sh),
                              donate_argnums=1
                              ).lower(params_shapes, cache, tokens, index)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

    mem = hlo_analysis.memory_summary(compiled)
    hlo_text = compiled.as_text()
    roof = hlo_analysis.analyze(compiled, chips, model_flops=mf,
                                hlo_text=hlo_text)
    if verbose:
        print(compiled.memory_analysis())
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        print({k: v for k, v in cost.items()
               if k in ("flops", "bytes accessed")})

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "run": {"fsdp": run.fsdp, "opt_8bit": run.opt_8bit,
                "remat": run.remat, "sync_mode": run.sync_mode,
                "moe_combine": run.moe_combine,
                "batch_axes": run.batch_axes,
                **(run_overrides or {})},
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "roofline": roof.to_dict(),
        "ok": True,
    }
    return rec


# ----------------------------------------------------------------------
def _results_path(tag: str) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    return os.path.join(RESULTS, f"dryrun_{tag}.json")


def load_results(tag: str = "baseline") -> dict:
    path = _results_path(tag)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_result(tag: str, key: str, rec: dict) -> None:
    data = load_results(tag)
    data[key] = rec
    with open(_results_path(tag), "w") as f:
        json.dump(data, f, indent=1)


def run_cells(archs, shapes, meshes, *, tag="baseline", force=False,
              run_overrides=None) -> None:
    done = load_results(tag)
    for arch in archs:
        cfg = configs.get(arch)
        app = applicable_shapes(cfg)
        for shape_name in shapes:
            if shape_name not in app:
                key = f"{arch}|{shape_name}|skip"
                if key not in done:
                    save_result(tag, key, {
                        "arch": arch, "shape": shape_name, "ok": False,
                        "skipped": "long_500k needs sub-quadratic attention"
                                   " (DESIGN.md §4)"})
                continue
            for mp in meshes:
                mesh_tag = "2x16x16" if mp else "16x16"
                key = f"{arch}|{shape_name}|{mesh_tag}"
                if key in done and done[key].get("ok") and not force:
                    print(f"[skip done] {key}")
                    continue
                print(f"[lower] {key} ...", flush=True)
                try:
                    rec = lower_cell(arch, shape_name, multi_pod=mp,
                                     run_overrides=run_overrides)
                    print(f"[ok] {key}: compile={rec['compile_s']}s "
                          f"dominant={rec['roofline']['dominant']} "
                          f"frac={rec['roofline']['roofline_fraction']:.3f}",
                          flush=True)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_tag, "ok": False,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    print(f"[FAIL] {key}: {type(e).__name__}: "
                          f"{str(e)[:200]}", flush=True)
                save_result(tag, key, rec)


def main(argv=None) -> None:
    # the production meshes need 512 host devices; the CPU backend makes
    # them only if this is set before its first use
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    compile_cache.enable()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--multi-pod", default="both",
                   choices=["no", "yes", "both"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--force", action="store_true")
    p.add_argument("--tag", default="baseline")
    p.add_argument("--set", action="append", default=[],
                   help="RunConfig override, e.g. --set fsdp=False")
    args = p.parse_args(argv)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = {"True": True, "False": False}.get(v, v) \
            if not v.lstrip("-").isdigit() else int(v)

    archs = [args.arch] if args.arch else sorted(configs.ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"no": [False], "yes": [True], "both": [False, True]}[
        args.multi_pod]
    run_cells(archs, shapes, meshes, tag=args.tag, force=args.force,
              run_overrides=overrides or None)


if __name__ == "__main__":
    main()
