"""Tests of ``scopes.py``, the split of a traced window by the program's
named scopes and host spans, on the CPU.

    python -m pytest benchmarks/tpu
"""
from __future__ import annotations

import gzip
import sys
from pathlib import Path

import pytest

pytest.importorskip("jax")

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import scopes  # noqa: E402
import trace_reduce as trace_lib  # noqa: E402

KW = {"window_start": bench.SPANS[0], "window_end": bench.SPANS[2]}


@pytest.mark.parametrize("op_name,path,pass_", [
    ("jit(train_step)/jvp(embed)/jit(_take)/gather", ("embed",), "forward"),
    ("jit(train_step)/jvp()/while/body/closed_call/attention/"
     "attention_core/closed_call/dot_general",
     ("attention", "attention_core"), "forward"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", ("mlp",), "recompute"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/"
     "transpose(jvp(ssm))/ssm_core/reduce_sum", ("ssm", "ssm_core"),
     "backward"),
    ("jit(train_step)/transpose(jvp(head))/dot_general", ("head",),
     "backward"),
    ("jit(train_step)/optimizer/mul", ("optimizer",), "other"),
    ("jit(train_step)/transpose(jvp())/while/body/dynamic_slice", (),
     "backward"),
    ("", (), "other"),
])
def test_scope_path_and_pass(op_name, path, pass_):
    assert scopes.scope_path(op_name) == path
    assert scopes.pass_of(op_name) == pass_


def test_innermost_split():
    spans = [("train", 0.0, 10.0), ("run_training.batch", 0.0, 2.0),
             ("run_training.wait", 3.0, 8.0), ("bench.x", 4.0, 5.0)]
    gaps = [[-1.0, 1.0], [2.0, 4.0], [9.0, 11.0]]
    got = scopes.innermost_split(gaps, spans[:3])
    assert got == pytest.approx({"no_span": 2.0, "run_training.batch": 1.0,
                                 "train": 2.0, "run_training.wait": 1.0})
    assert sum(got.values()) == pytest.approx(trace_lib.length(gaps))


HLO = """\
HloModule jit_train_step

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.3 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused.3, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/attention_core/dot_general"}
  %fusion.4 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused.4, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused.5, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/dynamic_update_slice"}
  ROOT %tuple.2 = (s32[], f32[8]) tuple(%c, %fusion.3)
}

%cond.1 (p: (s32[], f32[8])) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused.1, metadata={op_name="jit(train_step)/jvp(embed)/gather"}
  %while.2 = (s32[], f32[8]) while(%t), condition=%cond.1, body=%body.1
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused.7, metadata={op_name="jit(train_step)/transpose(jvp(head))/dot_general"}
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused.8, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""


def test_reduce_scopes_synthetic_trace():
    index = trace_lib.hlo_index(HLO)
    # two steps of 10 s on two devices; each step's device 0 idles in
    # [0, 1] (before the step span, in it, in batch), [7, 8] (wait) and
    # [8.5, 10] (in the step span, in on_step)
    host = []
    for t in (0.0, 10.0):
        host += [("train", t + 0.25, t + 10.0),
                 ("run_training.batch", t + 0.5, t + 1.0),
                 ("bench.batch_at", t, t + 1.0),
                 ("run_training.step", t + 1.0, t + 1.5),
                 ("run_training.wait", t + 1.5, t + 8.0),
                 ("run_training.on_step", t + 9.0, t + 10.0),
                 ("bench.on_step", t + 9.0, t + 10.0)]
    ops = [("fusion.1", 1.0, 2.0), ("while.2", 2.0, 6.0),
           ("fusion.3", 2.0, 4.0), ("fusion.4", 4.0, 5.0),
           ("fusion.5", 5.0, 6.0), ("fusion.7", 6.0, 7.0),
           ("fusion.8", 8.0, 8.5)]
    dev0 = [(n, t + s, t + e) for t in (0.0, 10.0) for n, s, e in ops]
    dev1 = [(n, s, e - 0.5 if n == "fusion.3" else e) for n, s, e in dev0]
    devs = {"/device:TPU:0": dev0, "/device:TPU:1": dev1}
    r = scopes.reduce_scopes(devs, host, index, **KW)
    base = trace_lib.reduce(devs, host, index, spans=bench.SPANS, **KW)
    assert r["steps"] == base["steps"] == 2
    ms = r["scope_ms"]
    assert ms == pytest.approx({"attention": 1750.0,
                                "attention_core": 1750.0, "mlp": 1000.0,
                                "embed": 1000.0, "head": 1000.0,
                                "optimizer": 500.0})
    assert r["blocks_unscoped"] == pytest.approx(1000.0)
    assert ms["attention"] + ms["mlp"] + r["blocks_unscoped"] == \
        pytest.approx(base["class_ms"]["blocks"])
    assert r["scope_pass_ms"]["mlp"] == pytest.approx({"recompute": 1000.0})
    assert r["scope_pass_ms"]["head"] == pytest.approx({"backward": 1000.0})
    assert r["scope_pass_ms"]["optimizer"] == pytest.approx({"other": 500.0})
    # per step: [0, 0.25] no span, [0.25, 0.5] and [8.5, 9] train,
    # [0.5, 1] batch, [7, 8] wait, [9, 10] on_step
    idle = r["program_idle_ms"]
    assert idle == pytest.approx({"no_span": 250.0,
                                  "run_training.batch": 500.0,
                                  "run_training.wait": 1000.0,
                                  "train": 750.0,
                                  "run_training.on_step": 1000.0})
    # device 0 is busy 6.5 s of each step's 10
    assert sum(idle.values()) == pytest.approx(3500.0)


def _recorded(name: str, tmp_path):
    data = HERE / "testdata"
    xplane = tmp_path / "t.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (data / f"{name}.xplane.pb.gz").read_bytes()))
    index = trace_lib.hlo_index(gzip.decompress(
        (data / f"{name}.hlo.txt.gz").read_bytes()).decode())
    devs, host = trace_lib.load_events(str(xplane))
    return devs, host, index


def test_unscoped_recorded_trace_names_nothing(tmp_path):
    """The trace recorded before the program had scopes and spans: no
    scope is found and every idle instant is ``no_span``."""
    devs, host, index = _recorded("deepseek-7b-l2.b8s4k", tmp_path)
    r = scopes.reduce_scopes(devs, host, index, **KW)
    base = trace_lib.reduce(devs, host, index, spans=bench.SPANS, **KW)
    assert r["scope_ms"] == {} and r["scope_pass_ms"] == {}
    assert r["blocks_unscoped"] == pytest.approx(base["class_ms"]["blocks"])
    assert list(r["program_idle_ms"]) == ["no_span"]


def test_scoped_recorded_trace(tmp_path):
    """A trace of 4 steps of ``deepseek-7b-l2.b8s4k`` with the program's
    scopes and spans, recorded on a TPU v5e (seed 7860006) from a step
    compiled with them (a compile cache filled by a program without them
    serves that program's metadata), with the step's HLO.  As in the
    unscoped trace, three steps fall inside the window."""
    devs, host, index = _recorded("deepseek-7b-l2.b8s4k.scoped", tmp_path)
    r = scopes.reduce_scopes(devs, host, index, **KW)
    base = trace_lib.reduce(devs, host, index, spans=bench.SPANS, **KW)
    assert r["steps"] == base["steps"] == 3
    ms, cls = r["scope_ms"], base["class_ms"]
    assert set(ms) == {"attention", "attention_core", "mlp", "embed",
                       "head", "loss", "optimizer"}
    # the block-level scopes and the rest of the loop make up the loop,
    # but for a scoped op hoisted out of it (0.26 us a step)
    assert ms["attention"] + ms["mlp"] + r["blocks_unscoped"] == \
        pytest.approx(cls["blocks"], abs=1e-3)
    assert ms["attention"] + ms["mlp"] >= 0.85 * cls["blocks"]
    assert ms["attention"] == pytest.approx(1056.0, abs=0.5)
    assert ms["attention_core"] == pytest.approx(796.2, abs=0.5)
    assert ms["mlp"] == pytest.approx(392.9, abs=0.5)
    for scope, by_pass in r["scope_pass_ms"].items():
        assert sum(by_pass.values()) == pytest.approx(ms[scope])
    assert set(r["scope_pass_ms"]["mlp"]) == {"forward", "backward",
                                              "recompute"}
    # the scoped parts of the head and optimizer classes
    assert ms["embed"] + ms["head"] + ms["loss"] <= cls["head"]
    assert ms["optimizer"] <= cls["optimizer"]
    # device idle by loop span: input, the loop's own time, and almost
    # nothing outside every span; the split sums to the idle time
    idle = r["program_idle_ms"]
    assert idle["run_training.batch"] > 0
    assert idle["run_training.wait"] > 0 and idle["train"] > 0
    assert idle["no_span"] <= 0.1 * sum(idle.values())
    assert sum(idle.values()) == pytest.approx(
        1e3 * (base["window_s"] - base["busy_s"]) / base["steps"])
