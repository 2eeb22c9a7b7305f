"""Small-mesh dry-run smoke: the full lowering machinery (sharding rules,
input specs, train/serve step assembly, roofline extraction) exercised on
an 8-device mesh in a subprocess, for one arch per family."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.jax]

_PROBE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.configs.base import RunConfig, ShapeConfig
from repro.launch import hlo_analysis, sharding as shard_lib
from repro.launch.mesh import dp_axes, make_mesh
from repro.launch.specs import decode_specs, input_specs
from repro.launch.train import (init_train_state, make_train_step,
                                model_flops, state_shardings)
from repro.launch.serve import make_serve_step
from repro.models import Model
from repro.optim import AdamW, AdamWConfig

out = {}
mesh = make_mesh((4, 2), ("data", "model"))
shape = ShapeConfig("tiny_train", 64, 8, "train")
dshape = ShapeConfig("tiny_decode", 64, 8, "decode")

for arch in ["deepseek-7b", "olmoe-1b-7b", "mamba2-130m",
             "whisper-large-v3", "internvl2-2b"]:
    cfg = dataclasses.replace(
        configs.get_smoke(arch), vocab_size=512)
    run = RunConfig(remat=True, microbatches=2)
    model = Model(cfg, run, mesh=mesh, dp_axes=dp_axes(mesh))
    rec = {}
    with mesh:
        opt = AdamW(AdamWConfig())
        ss = jax.eval_shape(lambda: init_train_state(
            model, opt, run, jax.random.PRNGKey(0)))
        batch = input_specs(cfg, shape)
        comp = jax.jit(make_train_step(model, opt, run),
                       in_shardings=(state_shardings(ss, cfg, run, mesh),
                                     shard_lib.batch_shardings(batch, mesh,
                                                               run)),
                       donate_argnums=0).lower(ss, batch).compile()
        roof = hlo_analysis.analyze(comp, 8,
                                    model_flops=model_flops(cfg, shape))
        rec["train"] = {"flops": roof.flops, "bytes": roof.hbm_bytes,
                        "coll": roof.coll_bytes,
                        "mem": hlo_analysis.memory_summary(comp)[
                            "peak_estimate_bytes"]}
        # decode
        ps = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        tokens, cache, index = decode_specs(model, cfg, dshape)
        comp2 = jax.jit(make_serve_step(model),
                        in_shardings=(
                            shard_lib.param_shardings(ps, cfg, run, mesh),
                            shard_lib.cache_shardings(cache, cfg, mesh),
                            shard_lib.batch_shardings(tokens, mesh, run),
                            NamedSharding(mesh, P())),
                        donate_argnums=1
                        ).lower(ps, cache, tokens, index).compile()
        roof2 = hlo_analysis.analyze(comp2, 8)
        rec["decode_flops"] = roof2.flops
    out[arch] = rec
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def probe():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", _PROBE],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         timeout=1200)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


ARCHS = ["deepseek-7b", "olmoe-1b-7b", "mamba2-130m", "whisper-large-v3",
         "internvl2-2b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_lowers_with_positive_terms(probe, arch):
    r = probe[arch]["train"]
    assert r["flops"] > 0 and r["bytes"] > 0
    assert r["mem"] > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_lowers(probe, arch):
    assert probe[arch]["decode_flops"] > 0


def test_train_has_collectives_on_multi_device_mesh(probe):
    # TP/grad reductions must appear for the dense arch
    assert probe["deepseek-7b"]["train"]["coll"] > 0
