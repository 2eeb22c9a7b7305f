"""Compiles for a described TPU v5e: the Pallas kernels at real widths
(flash attention's forward, dq and dk/dv at deepseek-7b-l2.b8s4k's shape,
and its gradient), deepseek-7b-l2.b8s4k's train step with the residuals
its remat keeps, and the full-width mamba2-130m train step (2 layers).

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
from repro.kernels import flash_attention as fa, ops  # noqa: E402
from repro.kernels.moe_gmm import gmm  # noqa: E402
from repro.kernels.ssd import ssd_intra_chunk  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402
from repro.models import model as model_lib  # noqa: E402

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


# deepseek-7b-l2.b8s4k: batch 8, 32 heads of 128, sequence 4096, bf16
B, S, H, HD = 8, 4096, 32, 128
_FLASH = dict(heads=(H, H), causal=True, scale=HD ** -0.5,
              block_q=fa.block_size(S), block_k=fa.block_size(S),
              interpret=False)
_QKV = ((B, S, H * HD), jnp.bfloat16)
_ROW = ((B, H, 1, S), jnp.float32)


def test_flash_attention_compiles(one_chip):
    _compile_kernel(lambda q, k, v: fa.flash_fwd(q, k, v, **_FLASH),
                    one_chip, _QKV, _QKV, _QKV)


@pytest.mark.parametrize("kernel", ["flash_dq", "flash_dkv"])
def test_flash_attention_backward_kernels_compile(one_chip, kernel):
    fn = getattr(fa, kernel)
    _compile_kernel(lambda *a: fn(*a, **_FLASH), one_chip,
                    _QKV, _QKV, _QKV, _QKV, _ROW, _ROW)


def test_flash_attention_gradient_compiles_in_its_scope(one_chip,
                                                        monkeypatch):
    """jax.grad through the custom_vjp: forward, dq and dk/dv kernels,
    each keeping the caller's ``attention_core`` scope in its op_name."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(q, k, v):
        with jax.named_scope("attention_core"):
            o = ops.flash_attention(q, k, v)
        return jnp.sum(o.astype(jnp.float32))

    shape = ((B, S, H, HD), jnp.bfloat16)
    compiled = _compile_kernel(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                               shape, shape, shape)
    calls = [ln for ln in compiled.as_text().splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 3
    assert all("attention_core" in ln for ln in calls), calls


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


def test_deepseek_step_keeps_core_and_mlp_on_one_chip(topo, monkeypatch,
                                                      device_memory):
    """deepseek-7b-l2.b8s4k's step as ``build_trainer`` builds it for a
    v5e: the budget keeps the kernel's output and the MLP products (not
    q, k, v), so the flash forward runs once a layer, not twice."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    device_memory(16_900_000_000)             # a v5e's limit (PERF.md §4)
    cfg = dataclasses.replace(configs.get("deepseek-7b"), n_layers=2,
                              vocab_size=12800)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    before = model_lib.REMAT.copy()
    trainer = build_trainer(cfg, RunConfig(sync_mode="barrier", remat=True),
                            mesh, batch=B, seq=S, steps=5000, lr=4.2e-4)
    compiled = trainer.step.lower(trainer.state_shapes,
                                  trainer.batch_shapes).compile()
    assert model_lib.REMAT - before == {"core,mlp": 3_430_940_672}
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 3


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
