"""The train step's named scopes and run_training's host spans, which a
profiler trace is split by (``repro.models.SCOPES``)."""
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402
from repro.models import SCOPES, Model  # noqa: E402
from repro.runtime import LoopConfig, run_training  # noqa: E402

pytestmark = [pytest.mark.jax]

_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
# the scopes one block of each mixer and FFN kind puts in the layer loop
BLOCK_SCOPES = {"attn": ("attention", "attention_core"),
                "mamba": ("ssm", "ssm_core"), "dense": ("mlp",),
                "moe": ("moe",), "none": ()}


def _trainer(arch: str, sync_mode: str):
    cfg = configs.get_smoke(arch)
    mesh = make_mesh((1, 1), ("data", "model"))
    # seq 512: the blockwise attention (256-row query blocks) runs
    trainer = build_trainer(cfg, RunConfig(sync_mode=sync_mode, remat=True),
                            mesh, batch=2, seq=512, steps=10, lr=1e-3)
    return cfg, trainer.step.lower(trainer.state_shapes,
                                   trainer.batch_shapes)


def _under(scope: str, op_name: str) -> bool:
    """``scope`` is a segment of ``op_name``'s path, bare or inside
    ``jvp(...)`` / ``transpose(jvp(...))``."""
    return re.search(rf"(^|/|\(){scope}(\)|/|$)", op_name) is not None


@pytest.mark.parametrize("arch", ["deepseek-7b", "mamba2-130m"])
def test_train_step_carries_its_scopes(arch):
    cfg, lowered = _trainer(arch, "barrier")
    names = set(_OPNAME_RE.findall(lowered.compile().as_text()))
    blocks = {s for seg in Model(cfg).segments for spec in seg.pattern
              for s in BLOCK_SCOPES[spec.mixer] + BLOCK_SCOPES[spec.ffn]}
    assert blocks and blocks <= set(SCOPES)
    passes = {
        "forward": lambda n: "jvp(" in n and "transpose(" not in n,
        "backward": lambda n: "transpose(" in n
        and "rematted_computation" not in n,
        "recompute": lambda n: "rematted_computation" in n,
    }
    for scope in sorted(blocks):
        in_loop = [n for n in names if _under(scope, n)
                   and "/while/body/" in n]
        for pass_, is_pass in passes.items():
            assert any(map(is_pass, in_loop)), (scope, pass_)
    for scope in ("embed", "head", "loss", "optimizer"):
        assert any(_under(scope, n) for n in names), scope
    # every scope found is one of SCOPES
    found = {s for n in names for s in SCOPES if _under(s, n)}
    assert found == blocks | {"embed", "head", "loss", "optimizer"}
    # the update is outside the differentiated function
    assert not [n for n in names if _under("optimizer", n) and "jvp(" in n]


def test_bucketed_sync_scopes_its_gradient_reduce():
    """The per-layer reduce is a sharding constraint; on one device it
    compiles to nothing, so the scope is read in the lowered module."""
    _, lowered = _trainer("mamba2-130m", "bucketed")
    text = lowered.as_text(debug_info=True)
    assert re.search(r'grad_sync/sharding_constraint', text)


def test_run_training_writes_step_and_phase_spans(tmp_path):
    step_fn = jax.jit(lambda s, b: (s + b.sum(), {"loss": s}))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        run_training(LoopConfig(total_steps=3, ckpt_dir=str(tmp_path / "ck"),
                                ckpt_every=2),
                     train_step=step_fn, init_state=lambda: jnp.zeros(()),
                     batch_at=lambda i: jnp.full((4,), float(i)),
                     on_step=lambda step, metrics: None)
    finally:
        jax.profiler.stop_trace()
    (path,) = (tmp_path / "trace").rglob("*.xplane.pb")
    events = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name == "train" or ev.name.startswith("run_training."))
    steps = [e for e in events if e[2] == "train"]
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2]
    phases = ["batch", "step", "wait", "record", "on_step"]
    for i, (lo, hi, _, _) in enumerate(steps):
        inside = [e[2] for e in events
                  if e[2] != "train" and lo <= e[0] and e[1] <= hi]
        # steps 1 and 2 save: every second step, and the last
        want = phases + (["save"] if i > 0 else [])
        assert inside == [f"run_training.{p}" for p in want]
    assert len(events) == 3 + 3 * len(phases) + 2
