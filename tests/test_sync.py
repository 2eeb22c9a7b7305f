"""MXDAG-planned gradient sync: numerical equivalence + HLO structure.

- bucketed (custom-vjp synced scan) must produce the SAME gradients as
  barrier (plain scan + XLA-placed reduction);
- on a multi-device mesh the bucketed backward must contain per-layer
  reduce-scatter/all-reduce INSIDE a while body, while barrier reduces
  after the loop (checked in a subprocess with 8 host devices so the main
  test process keeps 1 device);
- plan_sync recovers ByteScheduler's lower-layer-first order and predicts
  a win exactly when the step is comm-bound (Fig. 6 / §4.1.1).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import RunConfig, SHAPES
from repro.launch.mesh import make_mesh
from repro.models import Model


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


class TestNumericalEquivalence:
    @pytest.mark.parametrize("arch", ["deepseek-7b", "olmoe-1b-7b",
                                      "mamba2-130m"])
    def test_bucketed_grads_match_barrier(self, arch, mesh):
        cfg = configs.get_smoke(arch)
        rng = jax.random.PRNGKey(0)
        batch = {"tokens": jax.random.randint(rng, (2, 16), 0,
                                              cfg.vocab_size)}

        grads = {}
        for mode in ("barrier", "bucketed"):
            m = Model(cfg, RunConfig(sync_mode=mode, remat=False),
                      mesh=mesh, dtype=jnp.float32)
            params = m.init(jax.random.PRNGKey(1))
            loss, g = jax.jit(jax.value_and_grad(
                lambda p: m.loss(p, batch)[0]))(params)
            grads[mode] = (float(loss), g)

        assert grads["barrier"][0] == pytest.approx(
            grads["bucketed"][0], rel=1e-5)
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_flatten_with_path(grads["barrier"][1])[0],
                jax.tree_util.tree_flatten_with_path(grads["bucketed"][1])[0]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                err_msg=str(pa))


_HLO_PROBE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, re, sys
import jax, jax.numpy as jnp
from repro import configs
from repro.configs.base import RunConfig
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.launch import sharding as shard_lib
from repro.launch.train import init_train_state, make_train_step, state_shardings
from repro.launch.specs import input_specs
from repro.optim import AdamW, AdamWConfig
from repro.launch.hlo_cost import parse_module, COLLECTIVES

cfg = configs.get_smoke("deepseek-7b")
import dataclasses
cfg = dataclasses.replace(cfg, n_layers=4, vocab_size=512)
mesh = make_mesh((4, 2), ("data", "model"))
out = {}
for mode in ("barrier", "bucketed"):
    run = RunConfig(sync_mode=mode, remat=True, attn_impl="xla")
    model = Model(cfg, run, mesh=mesh, dp_axes=("data",))
    opt = AdamW(AdamWConfig())
    with mesh:
        ss = jax.eval_shape(lambda: init_train_state(
            model, opt, run, jax.random.PRNGKey(0)))
        batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        comp = jax.jit(make_train_step(model, opt, run),
                       in_shardings=(state_shardings(ss, cfg, run, mesh),
                                     shard_lib.batch_shardings(batch, mesh)),
                       ).lower(ss, batch).compile()
    comps, entry = parse_module(comp.as_text())
    # collectives inside while bodies vs at top level
    body_names = set()
    for c in comps.values():
        for op in c.ops:
            if op.kind == "while":
                m = re.search(r"body=%([\w.\-]+)", op.attrs)
                if m:
                    body_names.add(m.group(1))
    inside = inside_bf16 = outside = 0
    for cname, c in comps.items():
        for op in c.ops:
            base = op.kind[:-6] if op.kind.endswith("-start") else op.kind
            if base in ("all-reduce", "reduce-scatter"):
                if cname in body_names:
                    inside += 1
                    if "bf16[" in op.out_type:
                        inside_bf16 += 1
                else:
                    outside += 1
    out[mode] = {"inside": inside, "inside_bf16": inside_bf16,
                 "outside": outside}
print(json.dumps(out))
"""


class TestHLOStructure:
    def test_bucketed_emits_collectives_inside_loop(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        res = subprocess.run([sys.executable, "-c", _HLO_PROBE],
                             capture_output=True, text=True, env=env,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert res.returncode == 0, res.stderr[-2000:]
        data = json.loads(res.stdout.strip().splitlines()[-1])
        # Both modes reduce per layer inside the loop: GSPMD places the
        # grad AR at its production point inside the reverse scan, i.e.
        # the Fig. 6 layer-wise structure is XLA's natural lowering for
        # scan-over-layers (a *refuted* hypothesis that barrier mode
        # would reduce once after the loop — recorded in EXPERIMENTS.md
        # §Perf).  The invariants that hold: in-loop reductions exist,
        # and the bucketed hook never adds collective traffic.
        assert data["bucketed"]["inside"] > 0, data
        assert data["bucketed"]["inside"] <= data["barrier"]["inside"] + 2, data


class TestPlan:
    def test_order_is_lower_layer_first(self):
        from repro.sync.plan import plan_sync
        cfg = configs.get("deepseek-7b")
        plan = plan_sync(cfg, SHAPES["train_4k"])
        idx = [int(name[4:]) for name in plan.order]
        assert idx == sorted(idx), plan.order

    def test_bucketed_predicted_when_comm_bound(self):
        from repro.sync.plan import plan_sync
        # deepseek-coder-33b dense on 256 chips: sync per layer is
        # comparable to compute -> overlap should win
        cfg = configs.get("deepseek-coder-33b")
        plan = plan_sync(cfg, SHAPES["train_4k"])
        assert plan.mode == "bucketed"
        assert plan.predicted_speedup > 1.0

    def test_plan_reports_both_predictions(self):
        from repro.sync.plan import plan_sync
        for arch in ("deepseek-7b", "olmoe-1b-7b"):
            plan = plan_sync(configs.get(arch), SHAPES["train_4k"])
            assert plan.predicted_bucketed > 0
            assert plan.predicted_barrier > 0
            assert plan.predicted_bucketed <= plan.predicted_barrier + 1e-9
