"""Tests of the benchmark's own parts, on the CPU at a small size.

    python -m pytest benchmarks/tpu

- The references draw the same weights as the program from a seed.
- A run of the harness, its look for a chip skipped, comes out correct
  with the program as it is, and not correct with the control (the
  reference in float8 e4m3 put in the program's place) or with each
  fault a training cell can have planted under the timed path: a step
  that returns its state unchanged, half of the batch left out (the mean
  over the rest), and the exchange between chips left out (each chip's
  update from its own quarter of the rows).
- The trace reduction, on a trace recorded on a TPU v5e.

The small sizes have limits of their own, set like the cells' from
readings at that size on the CPU, seeds 1-6 (loss_gap, grad_gap,
change_gap):

- small Mamba-2: the program's largest 4.6e-5, 9.8e-3, 2.1e-2; the
  control's smallest 7.6e-5, 3.5e-2, 8.0e-2;
- small Llama: the program's largest 2.6e-4, 1.2e-3, 6.9e-3; the
  control's smallest 1.2e-3, 5.7e-3, 9.6e-3.
"""
from __future__ import annotations

import copy
import gzip
import json
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "references"))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402
import check  # noqa: E402
import spec  # noqa: E402
import trace_reduce as trace_lib  # noqa: E402

SMALL_LIMITS = {
    "mamba2": {"limits": {"loss_gap": 1e-4, "grad_gap": 2e-2,
                          "change_gap": 5e-2}},
    "llama": {"limits": {"loss_gap": 6e-4, "grad_gap": 3e-3,
                         "change_gap": 1.5e-2}},
}
MIX = {"batch": 8, "seq": 64, "model_axis": 1, "vocab": None,
       "noise_prob": 0.05, "checked_steps": 3, "warmup_steps": 5,
       "trace_steps": 2}


def small_mamba() -> dict:
    c = json.loads((HERE / "configs" / "mamba2-130m.json").read_text())
    c.update({"d_model": 64, "n_layer": 2, "vocab_size": 256})
    c["ssm_cfg"] = dict(c["ssm_cfg"], d_state=16, headdim=16, chunk_size=16)
    c["program"] = {"base": "mamba2-130m", "replace": {
        "n_layers": 2, "d_model": 64, "vocab_size": 256, "ssm_state": 16,
        "ssm_head_dim": 16, "ssm_chunk": 16}}
    c["reference_rows"] = 4
    return c


def small_llama() -> dict:
    c = json.loads((HERE / "configs" / "deepseek-7b-l2.json").read_text())
    c.update({"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "vocab_size": 256})
    c["program"] = {"base": "deepseek-7b", "replace": {
        "n_layers": 2, "d_model": 64, "d_ff": 128, "n_heads": 4,
        "n_kv_heads": 4, "head_dim": 16, "vocab_size": 256,
        "norm_eps": 1e-6}}
    c["reference_rows"] = 4
    return c


def small_cell(config: dict) -> spec.Cell:
    bench_json = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    return spec.Cell(name="small", chips=1, config=config, mix=dict(MIX),
                     limits=copy.deepcopy(SMALL_LIMITS[config["reference"]]),
                     end_to_end=bench_json["end_to_end"], per_layer=[])


def cpu() -> list:
    if jax.devices()[0].platform != "cpu":
        pytest.skip("these tests run on the CPU")
    return jax.devices()[:1]


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("make", [small_mamba, small_llama])
def test_reference_draws_the_programs_weights(make):
    from repro.configs.base import RunConfig
    from repro.models import Model
    config = make()
    ref = spec.reference(config["reference"])
    cfg = bench.program_config(config, ref)
    prog = Model(cfg, RunConfig()).init(jax.random.PRNGKey(2 ** 31 + 5))
    mine = ref.init(config, 2 ** 31 + 5)
    assert jax.tree.structure(prog) == jax.tree.structure(mine)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(mine)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool(jnp.all(a == b))


@pytest.mark.parametrize("make", [small_mamba, small_llama])
def test_seeded_init_is_the_trainers_state(make):
    from repro.configs.base import RunConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_trainer
    config = make()
    cfg = bench.program_config(config, spec.reference(config["reference"]))
    run_cfg = RunConfig(sync_mode="barrier", remat=True)
    mesh = make_mesh((1, 1), ("data", "model"), devices=cpu())
    seed = 2 ** 31 + 5
    trainer = build_trainer(cfg, run_cfg, mesh, batch=2, seq=32, steps=10,
                            lr=1e-3, seed=seed)
    mine = bench.seeded_init(cfg, run_cfg, mesh, trainer)(seed)
    theirs = trainer.init_state()
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))


@pytest.mark.parametrize("make", [small_mamba, small_llama])
def test_program_correct_and_control_not(make):
    cell = small_cell(make())
    r = bench.run(cell, 3, 0.5, False, cpu(),
                  controls={"fp8": {"precision": "fp8"}})
    assert r["correct"], r["checks"]
    ok, _ = check.judge(r["controls"]["fp8"], cell.limits)
    assert not ok, r["controls"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}


def _unchanged(step, trainer):
    def f(state, batch):
        keep = jax.tree.map(jnp.copy, state)
        _, metrics = step(state, batch)
        return keep, metrics
    return f


def _rows(fraction):
    def wrap(step, trainer):
        def f(state, batch):
            toks = batch["tokens"]
            return trainer.step(state, {"tokens": toks[:int(
                toks.shape[0] * fraction)]})
        return f
    return wrap


@pytest.mark.parametrize("fault", [_unchanged, _rows(0.5), _rows(0.25)],
                         ids=["state_unchanged", "half_batch",
                              "exchange_left_out"])
def test_planted_fault_is_not_correct(fault):
    r = bench.run(small_cell(small_mamba()), 4, 0.5, False, cpu(),
                  wrap_step=fault)
    assert not r["correct"], r["checks"]


def test_no_chip_exits_nonzero(capsys):
    cpu()
    assert bench.main(["--workload", "deepseek-7b-l2.b8s4k", "--seed", "1",
                       "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_every_cell_finds_its_files():
    bench_json = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    for w in bench_json["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.limits is not None, w["name"]
        ref = spec.reference(cell.config["reference"])
        bench.program_config(cell.config, ref)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m["name"]).read)
    src = (HERE / "bench.py").read_text()
    for name in ([w["name"] for w in bench_json["workloads"]]
                 + [m["name"] for m in bench_json["end_to_end"]
                    + bench_json["per_layer"]]):
        assert name not in src, name


def test_judge_compares_the_numbers_the_limits_name():
    nums = {"loss_gap": 1e-3, "first_loss_gap": 1e-5, "grad_gap": 0.2,
            "median_grad_gap": 1e-3, "change_gap": 0.05,
            "median_change_gap": 1e-3}
    ok, checks = check.judge(nums, {"limits": {"first_loss_gap": 1e-4}})
    assert ok and checks == {"first_loss_gap": {"value": 1e-5,
                                                "limit": 1e-4}}
    assert not check.judge(nums, {"limits": {"loss_gap": 1e-4}})[0]
    assert not check.judge(dict(nums, first_loss_gap=float("nan")),
                           {"limits": {"first_loss_gap": 1e-4}})[0]
    assert not check.judge(nums, {"limits": {}})[0]
    assert not check.judge(nums, None)[0]


def test_fp8act_rounds_the_residual_stream_both_ways():
    import common
    x = jnp.linspace(-3.0, 3.0, 101)
    for name, rounds in (("f32", False), ("fp8", False), ("fp8act", True)):
        y, vjp = jax.vjp(common.Policy(name).act, x)
        (g,) = vjp(x)
        assert bool(jnp.any(y != x)) == rounds, name
        assert bool(jnp.any(g != x)) == rounds, name
        # e4m3 keeps 3 bits of mantissa: under 1/16 of the value off
        assert float(jnp.max(jnp.abs(y - x) / (jnp.abs(x) + 1e-3))) < 1 / 16


# ---------------------------------------------------------------------------
def test_interval_arithmetic():
    u = trace_lib.union([(0, 2), (1, 3), (5, 6)])
    assert u == [[0, 3], [5, 6]]
    assert trace_lib.subtract([[0, 10]], u) == [[3, 5], [6, 10]]
    assert trace_lib.subtract([[0, 3], [5, 6]], [[1, 2], [5.5, 7]]) == \
        [[0, 1], [2, 3], [5, 5.5]]
    assert trace_lib.length(u) == 4


HLO = """\
HloModule jit_train_step

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.3 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused.3, metadata={op_name="jit(train_step)/jvp(while)/body/dot_general"}
  ROOT %tuple.2 = (s32[], f32[8]) tuple(%c, %fusion.3)
}

%cond.1 (p: (s32[], f32[8])) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused.1, metadata={op_name="jit(train_step)/jvp(embed)/gather"}
  %while.2 = (s32[], f32[8]) while(%t), condition=%cond.1, body=%body.1
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused.7, metadata={op_name="jit(train_step)/transpose(jvp(head))/dot_general"}
  %all-reduce.4 = f32[8]{0} all-reduce(%fusion.7), replica_groups={{0,1}}, to_apply=%add
  %fusion.8 = f32[8]{0} fusion(%all-reduce.4), kind=kLoop, calls=%fused.8, metadata={op_name="jit(train_step)/mul"}
}
"""


def test_hlo_classes():
    index = trace_lib.hlo_index(HLO)
    cls = {n: trace_lib.op_class(n, index) for n in
           ("fusion.1", "fusion.3", "while.2", "fusion.7", "all-reduce.4",
            "fusion.8", "copy.99")}
    assert cls == {"fusion.1": "head", "fusion.3": "blocks",
                   "while.2": "container", "fusion.7": "head",
                   "all-reduce.4": "collective", "fusion.8": "optimizer",
                   "copy.99": "other"}


def test_reduce_synthetic_trace():
    index = trace_lib.hlo_index(HLO)
    # one step from 0 to 10 on two devices; the host makes the batch in
    # [0, 1] and waits in on_step [9, 10]
    host = [("bench.batch_at", 0.0, 1.0), ("bench.step", 1.0, 1.5),
            ("bench.on_step", 9.0, 10.0)]
    dev0 = [("fusion.1", 1.0, 2.0), ("while.2", 2.0, 6.0),
            ("fusion.3", 2.0, 6.0), ("fusion.7", 6.0, 7.0),
            ("all-reduce.4", 6.5, 8.0), ("fusion.8", 8.0, 9.0)]
    dev1 = [(n, s + 0.5, e + 0.5) for n, s, e in dev0]
    r = trace_lib.reduce({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                         host, index, window_start="bench.batch_at",
                         window_end="bench.on_step", spans=bench.SPANS)
    assert r["steps"] == 1 and r["window_s"] == 10.0
    assert r["busy_s"] == 8.0                      # 1..9 and 1.5..9.5
    assert r["idle_pct"] == pytest.approx(20.0)
    assert r["class_ms"] == {"blocks": 4000.0, "collective": 1500.0,
                             "head": 2000.0, "optimizer": 1000.0}
    assert r["exposed_collective_ms"] == pytest.approx(1000.0)  # 7..8
    # device 0 idles in [0, 1] (batch_at) and [9, 10] (on_step)
    assert r["idle_gaps"] == [["bench.batch_at", 1.0],
                              ["bench.on_step", 1.0]]
    assert r["device_ops"][0] == ["fusion.3 [blocks]", 4.0]


def test_reduce_recorded_trace(tmp_path):
    """A trace of 4 steps of ``deepseek-7b-l2.b8s4k`` recorded on a TPU
    v5e (seed 104), with the step's HLO.  By hand, from its "XLA
    Modules" line: three whole steps of 1.5715 s each fall inside the
    window (the fourth step's on_step span ends after stop_trace), the
    window is 4.7252 s from the first batch_at to the last on_step, and
    the device is idle only between modules."""
    data = HERE / "testdata"
    xplane = tmp_path / "t.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (data / "deepseek-7b-l2.b8s4k.xplane.pb.gz").read_bytes()))
    index = trace_lib.hlo_index(gzip.decompress(
        (data / "deepseek-7b-l2.b8s4k.hlo.txt.gz").read_bytes()).decode())
    devs, host = trace_lib.load_events(str(xplane))
    assert list(devs) == ["/device:TPU:0"]
    r = trace_lib.reduce(devs, host, index, window_start=bench.SPANS[0],
                         window_end=bench.SPANS[2], spans=bench.SPANS)
    assert r["devices"] == 1 and r["steps"] == 3
    assert r["window_s"] == pytest.approx(4.7252, abs=1e-4)
    # busy: the union of op intervals, a little under the three modules'
    # 4.7145 s, since ops leave small gaps inside a module
    assert 4.70 < r["busy_s"] <= 4.7146
    assert r["idle_pct"] == pytest.approx(0.23, abs=0.01)
    ms = r["class_ms"]
    assert set(ms) == {"blocks", "head", "optimizer"}
    assert sum(ms.values()) == pytest.approx(1571.5, abs=1.0)
    assert ms["blocks"] == pytest.approx(1479.5, abs=0.5)
    assert ms["head"] == pytest.approx(72.1, abs=0.5)
    assert ms["optimizer"] == pytest.approx(19.9, abs=0.5)
    assert r["exposed_collective_ms"] is None
    assert r["device_ops"][0][0].endswith("[blocks]")
    assert {t for t, _ in r["idle_gaps"]} <= set(bench.SPANS) | {"no_span"}


def test_reduce_recorded_four_chip_trace(tmp_path):
    """A trace of 6 steps of mamba2-130m on a 4x1 data mesh with bucketed
    sync, recorded on four TPU v5e chips (seed 4102), with the step's
    HLO.  By hand: five steps start inside the window; on each device
    the all-reduces (51 a step on TPU:0: one per layer bucket inside the
    layer loop and a few outside) are ops of the device's one "XLA Ops"
    line, as synchronous as the compute around them, so no compute op
    overlaps any of them and all their time is exposed."""
    data = HERE / "testdata"
    xplane = tmp_path / "t.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (data / "mamba2-130m.dp4.b8s2k.xplane.pb.gz").read_bytes()))
    index = trace_lib.hlo_index(gzip.decompress(
        (data / "mamba2-130m.dp4.b8s2k.hlo.txt.gz").read_bytes()).decode())
    devs, host = trace_lib.load_events(str(xplane))
    assert sorted(devs) == [f"/device:TPU:{i}" for i in range(4)]
    r = trace_lib.reduce(devs, host, index, window_start=bench.SPANS[0],
                         window_end=bench.SPANS[2], spans=bench.SPANS)
    assert r["devices"] == 4 and r["steps"] == 5
    assert r["window_s"] == pytest.approx(0.3882, abs=1e-4)
    ms = r["class_ms"]
    assert set(ms) == {"blocks", "collective", "head", "optimizer"}
    assert ms["collective"] == pytest.approx(5.89, abs=0.01)
    assert r["exposed_collective_ms"] == pytest.approx(ms["collective"])
    assert r["idle_pct"] == pytest.approx(5.65, abs=0.01)
    lo = min(s for n, s, _ in host if n == bench.SPANS[0])
    hi = max(e for n, _, e in host if n == bench.SPANS[2])
    coll = [o for o in devs["/device:TPU:0"] if o[2] > lo and o[1] < hi
            and trace_lib.op_class(o[0], index) == "collective"]
    assert len(coll) == 5 * 51
