"""What the layer scan's remat keeps: the named residuals of
``model.REMAT_TIERS``, the budget that picks them (``remat_saves``), and
the programs that carry no names."""
import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core

from repro import configs
from repro.configs.base import RunConfig
from repro.kernels import ops
from repro.launch import train
from repro.launch.mesh import make_mesh
from repro.models import Model, layers
from repro.models import model as model_lib
from repro.optim import AdamW, AdamWConfig

pytestmark = [pytest.mark.jax]

TIER_SETS = [(), ("core",), ("core", "mlp"), ("core", "mlp", "qkv")]


def _ops(jaxpr, acc=None) -> collections.Counter:
    """Primitives of a jaxpr and every jaxpr inside it; a ``pallas_call``
    counts by its kernel's name (``pallas:_fwd_kernel``)."""
    acc = collections.Counter() if acc is None else acc
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            kernel = e.params["jaxpr"].debug_info.func_src_info.split()[0]
            acc["pallas:" + kernel] += 1
            continue
        acc[e.primitive.name] += 1
        for sub in jax_core.jaxprs_in_params(e.params):
            _ops(sub, acc)
    return acc


def _model(cfg, tiers, **run):
    m = Model(cfg, RunConfig(remat=True, **run),
              mesh=make_mesh((1, 1), ("data", "model")))
    m.saved_tiers = tiers
    return m


def _step(m, batch):
    """The model's jitted train step and its arguments."""
    params = m.init(jax.random.PRNGKey(0))
    opt = AdamW(AdamWConfig(lr=1e-3))
    state = {"params": params, "opt": opt.init(params)}
    return jax.jit(train.make_train_step(m, opt, m.run)), (state, batch)


def _lowered(step, args) -> str:
    """The step's StableHLO, its functions renamed in the order they
    appear: the names' counters differ between otherwise equal programs."""
    names = {}
    return re.sub(r"@[\w.$-]+",
                  lambda m: names.setdefault(m[0], f"@f{len(names)}"),
                  step.lower(*args).as_text())


def _unnamed(monkeypatch):
    """The program as it was before the residuals had names."""
    for mod in (layers, ops):
        monkeypatch.setattr(mod, "checkpoint_name", lambda x, name: x)


@pytest.fixture(scope="module")
def llama():
    """A small dense SwiGLU model on the flash kernels (interpreted), its
    batch, and the loss and gradients under full remat."""
    cfg = dataclasses.replace(configs.get_smoke("deepseek-7b"), n_layers=2)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 256),
                                          0, cfg.vocab_size)}
    full = _grads(_model(cfg, (), attn_impl="pallas"), batch)
    return cfg, batch, full


def _grads(m, batch):
    params = m.init(jax.random.PRNGKey(0))
    return jax.jit(jax.value_and_grad(lambda p, b: m.loss(p, b)[0]))(
        params, batch)


@pytest.mark.parametrize("tiers", TIER_SETS,
                         ids=lambda t: ",".join(t) or "none")
def test_saved_tiers_drop_their_recompute(llama, monkeypatch, tiers):
    """Each kept tier removes its recompute from the step: the saved core
    the second flash forward, the saved MLP products two matmuls and the
    saved q, k, v their three projections, in the scan's one body.  With
    nothing kept the step is today's program; the numbers never move."""
    cfg, batch, (loss0, grads0) = llama
    m = _model(cfg, tiers, attn_impl="pallas")
    before = model_lib.REMAT.copy()
    step, args = _step(m, batch)
    traced = step.trace(*args)
    kept = model_lib.REMAT - before
    counts = _ops(traced.jaxpr.jaxpr)
    assert counts["pallas:_fwd_kernel"] == (1 if "core" in tiers else 2)
    assert counts["pallas:_dq_kernel"] == counts["pallas:_dkv_kernel"] == 1
    full = _ops(_step(_model(cfg, (), attn_impl="pallas"),
                      batch)[0].trace(*args).jaxpr.jaxpr)
    assert full["dot_general"] - counts["dot_general"] == \
        2 * ("mlp" in tiers) + 3 * ("qkv" in tiers)
    if tiers:
        bytes_ = m.tier_bytes(m.segments[0], 2, 256)
        assert kept == {",".join(tiers): sum(bytes_[t] for t in tiers)}
    else:
        assert not kept
        text = _lowered(step, args)
        _unnamed(monkeypatch)
        assert _lowered(_step(m, batch)[0], args) == text

    loss, grads = _grads(m, batch)
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-6)
    for g, g0 in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(g0, np.float32),
                                   rtol=1e-5, atol=1e-7)


def test_mamba2_step_is_unchanged(monkeypatch):
    """The SSM mixer carries no names.  The smoke config's step (its
    reduced config adds a small MLP) lowers as before the names; with
    mamba2-130m's own ``d_ff = 0`` no tier exists, so keeping every tier
    leaves the program as it is."""
    cfg = configs.get_smoke("mamba2-130m")
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32)}
    step, args = _step(_model(cfg, ()), batch)
    text = _lowered(step, args)

    bare = dataclasses.replace(cfg, d_ff=0)
    step, args = _step(_model(bare, ()), batch)
    assert _ops(step.trace(*args).jaxpr.jaxpr)["name"] == 0
    bare_text = _lowered(step, args)
    before = model_lib.REMAT.copy()
    step, _ = _step(_model(bare, ("core", "mlp", "qkv")), batch)
    assert _lowered(step, args) == bare_text
    assert model_lib.REMAT == before

    _unnamed(monkeypatch)
    step, args = _step(_model(cfg, ()), batch)
    assert _lowered(step, args) == text


def _deepseek_7b_l2(monkeypatch):
    """deepseek-7b-l2.b8s4k's bytes per tier, as a TPU would take the
    kernels (nothing is compiled)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(configs.get("deepseek-7b"), n_layers=2,
                              vocab_size=12800)
    m = Model(cfg, RunConfig())
    (seg,) = m.segments
    return m.tier_bytes(seg, 8, 4096)


def test_tier_bytes_of_the_benchmark_cell(monkeypatch):
    got = _deepseek_7b_l2(monkeypatch)
    o, lse = 8 * 4096 * 4096 * 2, 8 * 32 * 4096 * 4
    assert got == {"core": 2 * (o + lse), "mlp": 2 * 2 * 8 * 4096 * 11008 * 2,
                   "qkv": 2 * 3 * o}
    assert sum(got.values()) == 5_041_553_408


@pytest.mark.parametrize("budget,kept", [
    (5.9e9, ("core", "mlp", "qkv")),
    (3.5e9, ("core", "mlp")),
    (2e9, ("core",)),
    (0.54e9, ()),
    (None, ()),
], ids=["5.9GB", "3.5GB", "2GB", "0.54GB", "no-memory-stats"])
def test_remat_saves_takes_tiers_in_order_while_they_fit(
        monkeypatch, budget, kept):
    assert model_lib.remat_saves(_deepseek_7b_l2(monkeypatch),
                                 budget) == kept


def test_remat_saves_passes_over_a_tier_the_model_lacks():
    assert model_lib.remat_saves({"core": 0, "mlp": 10, "qkv": 5}, 12) == \
        ("mlp",)


@pytest.mark.parametrize("limit,kept", [
    (None, None), (10 ** 12, "mlp"), (0, None)],
    ids=["cpu", "room", "full"])
def test_build_trainer_budgets_from_device_memory(device_memory, limit,
                                                  kept):
    """On the CPU (no memory stats) nothing is kept.  Where the device
    reports its limit, a third of what the state leaves is the budget;
    the smoke llama on the XLA attention path names only its MLP."""
    if limit is not None:
        device_memory(limit)
    cfg = configs.get_smoke("deepseek-7b")
    mesh = make_mesh((1, 1), ("data", "model"))
    before = model_lib.REMAT.copy()
    trainer = train.build_trainer(cfg, RunConfig(sync_mode="barrier"), mesh,
                                  batch=2, seq=256, steps=2, lr=1e-3)
    trainer.step.trace(trainer.state_shapes, trainer.batch_shapes)
    got = model_lib.REMAT - before
    assert list(got) == ([kept] if kept else [])

