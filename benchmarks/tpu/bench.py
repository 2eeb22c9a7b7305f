#!/usr/bin/env python3
"""Runs one benchmark cell once on the chips of this machine.

    python3 benchmarks/tpu/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix, limits and metric readers are files found by
name (see ``spec.py``).  A run does what the normal training path does:
``plan_sync`` picks the gradient-sync mode for the cell's chips,
``build_trainer`` builds the step on ``make_mesh``, and
``run_training`` drives it on batches made from ``--seed``, with the
weights drawn from the seed on the device.

Set-up compiles the cell's step (through the persistent compile cache)
and runs the warm-up steps; the first ``checked_steps`` of them are the
ones the plain reference follows.  The window then runs for about
``--seconds``: from the end of the last warm-up step to the end of the
last step, both read after the step's outputs are ready.  With
``--trace 1`` a few more steps follow under the profiler.  No
checkpoint is written (see PERF.md).  Once the window has closed and
the program's state is freed, the reference follows the checked steps
and decides ``correct``.

Earlier lines of stdout give the sync plan beside the measured step,
compiles inside the window, the parts of the set-up, the slowest steps
of the window, the step's compiled memory beside the device's peak, and
the FLOPs per step; the last line is the result as one JSON object.
``--keep-trace <dir>`` keeps the raw trace and the step's HLO text, the
way ``testdata/`` is recorded.  The compared numbers with their limits are the last
lines of stderr.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import check  # noqa: E402
import peaks  # noqa: E402
import spec  # noqa: E402
import trace_reduce as trace_lib  # noqa: E402
import traffic  # noqa: E402

SPANS = ("bench.batch_at", "bench.step", "bench.on_step")


class NoChip(RuntimeError):
    pass


class _WindowClosed(Exception):
    pass


def chip_devices(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, jax found "
                     f"{len(devices)}")
    return devices[:chips]


def _out(msg: str) -> None:
    print(msg, flush=True)


def _leaf_names(tree) -> list[str]:
    sys.path.insert(0, str(HERE / "references"))
    from common import leaf_names
    return leaf_names(tree)


def program_config(config: dict, ref):
    """The program's ArchConfig for a configuration file, checked against
    the sizes the file states."""
    from repro import configs
    prog = config["program"]
    cfg = dataclasses.replace(configs.get(prog["base"]), name=config["name"],
                              **prog.get("replace", {}))
    have = dataclasses.asdict(cfg)
    bad = {k: (have[k], v) for k, v in ref.expect(config).items()
           if have[k] != v}
    if bad:
        raise ValueError(f"{config['name']}: the program's config differs "
                         f"from the file (program, file): {bad}")
    return cfg


def seeded_init(cfg, run_cfg, mesh, trainer):
    """``seed -> state``: the trainer's initial state drawn by the
    program's own ``init_train_state`` from ``PRNGKey(seed)``, with the
    key an argument of one jitted call.  ``build_trainer`` folds the seed
    into its init program as a constant, so every new seed would compile
    that program anew inside the set-up; this one compiles once and is
    found in the cache by every later run."""
    import jax
    from repro.launch.mesh import dp_axes
    from repro.launch.train import init_train_state
    from repro.models import Model
    from repro.optim import AdamW, AdamWConfig

    model = Model(cfg, run_cfg, mesh=mesh, dp_axes=dp_axes(mesh))
    opt = AdamW(AdamWConfig(lr=0.0))      # the rate is not in the state
    init = jax.jit(lambda key: init_train_state(model, opt, run_cfg, key),
                   out_shardings=trainer.state_shardings)
    want = trainer.state_shapes
    have = jax.eval_shape(init, jax.random.PRNGKey(0))
    if jax.tree.structure(have) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(have), jax.tree.leaves(want))):
        raise ValueError("the seeded init differs from the trainer's state")
    return lambda seed: init(jax.random.PRNGKey(seed))


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        devices: list, *, wrap_step=None, controls=None,
        keep_trace=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object.
    ``wrap_step(step, trainer)`` may put another step in the compiled
    one's place (the fault tests plant faults there).  ``controls``
    maps a label to ``readings`` arguments of the reference (a lower
    precision, part of the batch); each is put in the program's place and
    its numbers go to ``result["controls"]``.  ``keep_trace`` is a
    directory that receives the raw trace and the step's HLO."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.launch import compile_cache
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_trainer
    from repro.runtime import LoopConfig, run_training
    from repro.sync.plan import plan_sync

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    marks = {"imports": time.monotonic()}

    config, mix = cell.config, cell.mix
    ref = spec.reference(config["reference"])
    cfg = program_config(config, ref)
    train = config["train"]
    batch, seq, tp = mix["batch"], mix["seq"], mix["model_axis"]
    chips = len(devices)
    n_checked, n_warm = mix["checked_steps"], mix["warmup_steps"]
    vocab = cfg.vocab_size

    plan = plan_sync(cfg, ShapeConfig(cell.name, seq, batch, "train"),
                     chips=chips, tp=tp)
    mesh = make_mesh((chips // tp, tp), ("data", "model"), devices=devices)
    run_cfg = RunConfig(sync_mode=plan.mode, remat=True)
    trainer = build_trainer(cfg, run_cfg, mesh, batch=batch, seq=seq,
                            steps=train["schedule_steps"], lr=train["lr"],
                            seed=seed)
    init_from = seeded_init(cfg, run_cfg, mesh, trainer)
    t = marks["build"] = time.monotonic()
    compiled = trainer.step.lower(trainer.state_shapes,
                                  trainer.batch_shapes).compile()
    compile_s = time.monotonic() - t
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    hlo_text = compiled.as_text() if trace else ""
    hlo_index = trace_lib.hlo_index(hlo_text) if trace else None
    step_fn = compiled if wrap_step is None else wrap_step(compiled, trainer)
    tok_sharding = trainer.batch_shardings["tokens"]

    # ---- the loop's callables -------------------------------------------
    rec = {"ends": {}, "input": {}, "loss": [], "nonfinite": 0,
           "calls": 0, "compiles": 0, "counting": False}
    snaps: dict = {}
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])

    def count_compiles(event: str, *_a, **_k):
        if rec["counting"] and event.startswith("/jax/core/compile/"):
            rec["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(count_compiles)

    def init_state():
        marks["init_start"] = time.monotonic()
        state = init_from(seed)
        jax.block_until_ready(state)
        marks["init"] = time.monotonic()
        snaps["p0"] = jax.device_get(state["params"])
        snaps["names"] = _leaf_names(state["params"])
        marks["copy_p0"] = time.monotonic()
        return state

    def batch_at(step: int) -> dict:
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(SPANS[0]):
            toks = traffic.mix_batch(mix, vocab, seed, step)
            arr = jax.make_array_from_callback(toks.shape, tok_sharding,
                                               lambda idx: toks[idx])
        rec["input"][step] = time.monotonic() - t0
        return {"tokens": arr}

    def train_step(state, batch_):
        with jax.profiler.TraceAnnotation(SPANS[1]):
            state, metrics = step_fn(state, batch_)
        i = rec["calls"]
        rec["calls"] += 1
        if i == 0:
            snaps["m1"] = norms(state["opt"]["m"])
        if i == n_checked - 1:
            t0 = time.monotonic()
            snaps["pn"] = jax.device_get(state["params"])
            marks["copy_pn_s"] = time.monotonic() - t0
        return state, metrics

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    window: dict = {}

    def on_step(step: int, metrics) -> None:
        now = time.monotonic()
        rec["ends"][step] = now
        with jax.profiler.TraceAnnotation(SPANS[2]):
            loss = float(metrics["loss"])
            if step < n_checked:
                rec["loss"].append(loss)
            if step == n_warm - 1:
                durs = [rec["ends"][s] - rec["ends"][s - 1]
                        for s in range(max(n_checked, 1), n_warm)]
                window["first"] = step + 1
                window["last"] = step + max(
                    1, round(seconds / statistics.median(durs)))
                window["t0"] = now
                rec["counting"] = True
            elif "t0" in window and "t1" not in window:
                rec["nonfinite"] += not math.isfinite(loss)
                if step == window["last"]:
                    window["t1"] = now
                    rec["counting"] = False
                    if not trace:
                        raise _WindowClosed
                    window["trace_last"] = step + mix["trace_steps"]
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts)
            elif "trace_last" in window and step == window["trace_last"]:
                jax.profiler.stop_trace()
                raise _WindowClosed

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        run_training(LoopConfig(total_steps=10 ** 9, ckpt_dir=ckpt_dir,
                                ckpt_every=10 ** 9),
                     train_step=train_step, init_state=init_state,
                     batch_at=batch_at,
                     state_shardings=trainer.state_shardings,
                     on_step=on_step)
    except _WindowClosed:
        pass
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    set_up = window["t0"] - T0
    win_s = window["t1"] - window["t0"]
    n_win = window["last"] - window["first"] + 1
    step_s = win_s / n_win

    # ---- what the run read, before anything else touches the device ----
    # the runtime reserves a loaded program's temporaries apart from the
    # arrays it counts in use; the footprint is the sum of the two peaks
    stats = [d.memory_stats() or {} for d in devices]
    used = [(s.get("peak_bytes_in_use", 0), s.get("peak_bytes_reserved", 0))
            for s in stats]
    peak = max(a + b for a, b in used)
    flops_tok = ref.flops_per_token(config, seq)
    _out(f"plan_sync: mode {plan.mode}, predicted barrier "
         f"{plan.predicted_barrier:.6f} s, bucketed "
         f"{plan.predicted_bucketed:.6f} s; measured step {step_s:.6f} s "
         f"(measured / predicted {step_s / min(plan.predicted_barrier, plan.predicted_bucketed):.3f})")
    _out(f"window: {n_win} steps in {win_s:.4f} s after {window['first']} "
         f"set-up steps; compiles inside the window: {rec['compiles']}")
    _out(f"compile or cache load of the step: {compile_s:.3f} s")
    warm = [rec["ends"][s] - (rec["ends"][s - 1] if s else marks["copy_p0"])
            for s in range(window["first"])]
    _out("set-up parts: "
         f"imports {marks['imports'] - T0:.3f} s, plan and build "
         f"{marks['build'] - marks['imports']:.3f} s, compile "
         f"{compile_s:.3f} s, until init "
         f"{marks['init_start'] - marks['build'] - compile_s:.3f}"
         f" s, init {marks['init'] - marks['init_start']:.3f} s, host copy "
         f"of the weights {marks['copy_p0'] - marks['init']:.3f} s and "
         f"{marks['copy_pn_s']:.3f} s, warm-up steps "
         + ", ".join(f"{d:.3f}" for d in warm) + " s")
    durs = {s: rec["ends"][s] - rec["ends"][s - 1]
            for s in range(window["first"], window["last"] + 1)}
    slow = sorted(durs, key=durs.get, reverse=True)[:3]
    _out(f"window steps: median {statistics.median(durs.values()):.6f} s; "
         "slowest " + ", ".join(
             f"step {s} {durs[s]:.6f} s (input {1e3 * rec['input'][s]:.3f}"
             " ms)" for s in slow))
    _out(f"memory: compiled arguments {mem.argument_size_in_bytes} B, "
         f"temporaries {mem.temp_size_in_bytes} B, outputs "
         f"{mem.output_size_in_bytes} B, aliased {mem.alias_size_in_bytes}"
         f" B; fullest chip: peak_bytes_in_use {max(used, key=sum)[0]} B"
         f" + peak_bytes_reserved {max(used, key=sum)[1]} B = {peak} B")
    _out(f"flops per step: model {flops_tok * batch * seq:.6e} (no "
         f"recompute), compiled cost_analysis {cost.get('flops', 0.0):.6e}"
         f" per device x {chips} device(s)")
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(trainer.state_shapes))
    _out(f"checkpoint save: not run; run_training's save would write the "
         f"state, {state_bytes} B, in every run (see PERF.md)")

    reduced = None
    if trace:
        files = list(Path(trace_dir).rglob("*.xplane.pb"))
        if files:
            devs, host = trace_lib.load_events(str(files[0]))
            reduced = trace_lib.reduce(
                devs, host, hlo_index, window_start=SPANS[0],
                window_end=SPANS[2], spans=SPANS)
    if keep_trace and trace:
        shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        Path(keep_trace, "step.hlo.txt").write_text(hlo_text)
    shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- the program's readings, then the reference's ---------------------
    names = snaps["names"]
    f32 = np.float32
    prog = {"loss": rec["loss"],
            "grad_norm": {n: float(x) / (1 - train["b1"])
                          for n, x in zip(names, snaps["m1"])},
            "change_norm": {
                n: float(np.linalg.norm(b.astype(f32).ravel()
                                        - a.astype(f32).ravel()))
                for n, a, b in zip(names, jax.tree.leaves(snaps["p0"]),
                                   jax.tree.leaves(snaps["pn"]))}}
    snaps.clear()
    del trainer, compiled, step_fn
    batches = [traffic.mix_batch(mix, vocab, seed, s)
               for s in range(n_checked)]
    t = time.monotonic()
    with jax.default_device(devices[0]):
        refr = ref.readings(config, batches, seed)
    ref_s = time.monotonic() - t
    numbers = check.gaps(prog, refr)
    correct, checks = check.judge(numbers, cell.limits)
    _out(f"reference: {n_checked} steps in {ref_s:.3f} s; losses "
         f"program {prog['loss']} reference {refr['loss']}")
    _out("numbers not compared: " + json.dumps(
        {k: v for k, v in numbers.items() if k not in checks}))
    ctrl, raw = {}, {"program": prog, "reference": refr}
    for label, kw in (controls or {}).items():
        with jax.default_device(devices[0]):
            raw[label] = ref.readings(config, batches, seed, **kw)
        ctrl[label] = check.gaps(raw[label], refr)

    # ---- metrics ---------------------------------------------------------
    dev = devices[0]
    ctx = {"set_up_seconds": set_up, "chips": chips, "trace": reduced,
           "flops_per_token": flops_tok,
           "peak_flops": peaks.peaks(dev.device_kind)["bf16_flops"]
           if dev.platform == "tpu" else float("nan"),
           "window": {"steps": n_win, "seconds": win_s,
                      "tokens": n_win * batch * seq,
                      "input_s": [rec["input"][s] for s in
                                  range(window["first"],
                                        window["last"] + 1)]}}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": n_win,
              "failed": rec["nonfinite"], "metrics": metrics,
              "device": device}
    if trace:
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            _out("trace: device ms per step by class "
                 + json.dumps(reduced["class_ms"]))
        else:
            _out("trace: no device op in the traced window")
    if controls:
        result["controls"] = ctrl
        result["raw"] = raw
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", default=None,
                   help="with --trace 1, copy the raw trace and the step's "
                        "HLO text into this directory")
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        devices = chip_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}; nothing measured", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 keep_trace=args.keep_trace)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
