"""Which path ``attention.sdpa`` takes, and a train step through the
flash kernels against the blockwise XLA path."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import RunConfig
from repro.launch.mesh import make_mesh
from repro.models import Model, attention

pytestmark = [pytest.mark.jax]


def _taken(monkeypatch, *, impl="auto", S=256, T=None, hd=128, hdv=None,
           H=4, K=2, causal=True, dtype=jnp.bfloat16, **kw):
    """Trace one ``sdpa`` call as a TPU would (nothing is compiled) and
    return the path the tally recorded."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T = S if T is None else T
    q = jax.ShapeDtypeStruct((2, S, H, hd), dtype)
    k = jax.ShapeDtypeStruct((2, T, K, hd), dtype)
    v = jax.ShapeDtypeStruct((2, T, K, hdv or hd), dtype)
    before = attention.DISPATCH.copy()
    jax.eval_shape(lambda q, k, v: attention.sdpa(
        q, k, v, causal=causal, impl=impl, **kw), q, k, v)
    taken = attention.DISPATCH - before
    assert sum(taken.values()) == 1, taken
    return next(iter(taken))


@pytest.mark.parametrize("case,path", [
    pytest.param(dict(), "kernel", id="causal-hd128"),
    pytest.param(dict(S=384), "kernel", id="three-blocks-of-128"),
    pytest.param(dict(H=4, K=4, q_positions=jnp.arange(256)), "kernel",
                 id="1d-positions"),
    pytest.param(dict(hd=192, hdv=128), "xla_flash", id="mla-heads"),
    pytest.param(dict(hd=64), "xla_flash", id="hd64"),
    pytest.param(dict(S=65536), "xla_flash", id="panels-too-long"),
    pytest.param(dict(S=200), "einsum", id="unaligned-seq"),
    pytest.param(dict(S=1, T=512, k_valid_len=jnp.full((2,), 9, jnp.int32)),
                 "einsum", id="decode"),
    pytest.param(dict(T=512, causal=False), "einsum", id="cross-attention"),
    pytest.param(dict(impl="xla_flash"), "xla_flash", id="forced-xla_flash"),
    pytest.param(dict(impl="xla"), "einsum", id="forced-xla"),
    pytest.param(dict(impl="pallas", hd=64), "kernel", id="forced-pallas"),
])
def test_auto_dispatch_on_tpu(monkeypatch, case, path):
    assert _taken(monkeypatch, **case) == path


def test_auto_keeps_xla_off_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    q = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    before = attention.DISPATCH.copy()
    jax.eval_shape(lambda q: attention.sdpa(q, q, q, causal=True,
                                            impl="auto"), q)
    assert attention.DISPATCH - before == {"xla_flash": 1}


def test_auto_keeps_xla_on_a_mesh_of_several_devices():
    cfg = configs.get_smoke("deepseek-7b")
    assert RunConfig().attn_impl == "auto"
    assert Model(cfg, RunConfig()).attn_impl == "auto"
    several = types.SimpleNamespace(size=4)
    assert Model(cfg, RunConfig(), mesh=several).attn_impl == "xla_flash"
    assert Model(cfg, RunConfig(attn_impl="pallas"),
                 mesh=several).attn_impl == "pallas"


def test_train_step_through_the_kernels_matches_xla_flash():
    """One loss and gradient of a tiny llama: the flash kernels
    (interpreted) against the blockwise XLA path, to bf16 tolerance."""
    cfg = dataclasses.replace(configs.get_smoke("deepseek-7b"), n_layers=2)
    mesh = make_mesh((1, 1), ("data", "model"))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 256),
                                          0, cfg.vocab_size)}
    out = {}
    for impl in ("pallas", "xla_flash"):
        m = Model(cfg, RunConfig(remat=True, attn_impl=impl), mesh=mesh)
        params = m.init(jax.random.PRNGKey(0))
        before = attention.DISPATCH.copy()
        out[impl] = jax.jit(jax.value_and_grad(
            lambda p, b: m.loss(p, b)[0]))(params, batch)
        taken = attention.DISPATCH - before
        assert set(taken) == {"kernel" if impl == "pallas" else impl}
    (loss, grads), (loss_x, grads_x) = out["pallas"], out["xla_flash"]
    np.testing.assert_allclose(float(loss), float(loss_x), rtol=2e-3)
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), gx in zip(leaves, jax.tree.leaves(grads_x)):
        g, gx = np.asarray(g, np.float32), np.asarray(gx, np.float32)
        scale = max(np.abs(gx).max(), 1e-12)
        np.testing.assert_allclose(g / scale, gx / scale, atol=3e-2,
                                   err_msg=jax.tree_util.keystr(path))
