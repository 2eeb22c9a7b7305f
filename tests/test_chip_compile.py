"""Compiles for a described TPU v5e: the three Pallas kernels at real
widths and the full-width mamba2-130m train step (2 layers).

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  Nothing runs, so these tests say nothing about
results or times; they catch what interpret mode cannot (block tiling,
VMEM limits, programs that do not fit the device).  The topology is
described inside a fixture, never while a module is imported: only one
process at a time may load the TPU library.
"""
import dataclasses

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.kernels.flash_attention import flash_attention_bhsd  # noqa: E402
from repro.kernels.moe_gmm import gmm  # noqa: E402
from repro.kernels.ssd import ssd_intra_chunk  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402

pytestmark = [pytest.mark.jax]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_described_chip_is_a_v5e(topo):
    assert topo.devices[0].device_kind == hlo_analysis.V5E
    assert hlo_analysis.peaks(topo.devices[0].device_kind).flops == 197e12


def test_flash_attention_compiles(one_chip):
    bf = jnp.bfloat16
    q = ((1, 32, 4096, 128), bf)
    _compile_kernel(lambda q, k, v: flash_attention_bhsd(
        q, k, v, causal=True, interpret=False), one_chip, q, q, q)


def test_gmm_compiles(one_chip):
    bf = jnp.bfloat16
    _compile_kernel(lambda x, w: gmm(x, w, interpret=False), one_chip,
                    ((64, 256, 2048), bf), ((64, 2048, 1024), bf))


def test_ssd_intra_chunk_compiles_at_mamba2_130m_widths(one_chip):
    cfg = configs.get("mamba2-130m")
    Q, P, N = cfg.ssm_chunk, cfg.ssm_head_dim, cfg.ssm_state
    H = cfg.ssm_expand * cfg.d_model // P
    nc = 2048 // Q
    bf, f32 = jnp.bfloat16, jnp.float32
    _compile_kernel(lambda *a: ssd_intra_chunk(*a, interpret=False),
                    one_chip, ((H, nc, Q, P), bf), ((H, nc, Q), f32),
                    ((H,), f32), ((1, nc, Q, N), bf), ((1, nc, Q, N), bf))


@pytest.mark.parametrize("sync_mode", ["barrier", "bucketed"])
def test_mamba2_train_step_compiles_on_one_chip(topo, sync_mode):
    """Full width (d_model 768, vocab 50280), 2 of 24 layers, batch 8 x
    seq 2048 — the step chip_smoke.py runs, through the same builder."""
    cfg = dataclasses.replace(configs.get("mamba2-130m"), n_layers=2)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    trainer = build_trainer(cfg, RunConfig(sync_mode=sync_mode, remat=True),
                            mesh, batch=8, seq=2048, steps=20, lr=3e-3)
    compiled = trainer.step.lower(trainer.state_shapes,
                                  trainer.batch_shapes).compile()
    mem = hlo_analysis.memory_summary(compiled)
    assert mem["argument_size_in_bytes"] > 0
    assert mem["fits_hbm"], mem
