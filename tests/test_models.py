"""Per-architecture smoke tests + decode-vs-prefill consistency.

Every assigned arch instantiates a REDUCED config of the same family and
runs one forward/train step on CPU asserting output shapes + no NaNs
(framework requirement).  Consistency tests verify that token-by-token
decoding with a KV/SSM cache reproduces the full-sequence forward logits —
this covers the GQA cache, the MLA *absorbed* decode path, partial-RoPE,
and the SSD single-step recurrence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import RunConfig
from repro.launch.mesh import make_mesh
from repro.models import Model, derive_segments

ALL_ARCHS = sorted(configs.ARCHS)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def make_batch(cfg, rng, B=2, S=16):
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    if cfg.encoder_layers:
        batch["audio_embeds"] = 0.01 * jnp.ones(
            (B, cfg.max_source_positions, cfg.d_model), jnp.bfloat16)
    if cfg.vision_embed_dim:
        batch["vision_embeds"] = 0.01 * jnp.ones(
            (B, cfg.vision_seq, cfg.vision_embed_dim), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch, mesh):
    """Reduced config: one loss+grad evaluation, finite, right shapes."""
    cfg = configs.get_smoke(arch)
    m = Model(cfg, RunConfig(remat=True), mesh=mesh)
    rng = jax.random.PRNGKey(0)
    params = m.init(rng)
    batch = make_batch(cfg, rng)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: m.loss(p, b)[0]))(params, batch)
    assert jnp.isfinite(loss), arch
    flat, _ = jax.tree.flatten(grads)
    assert all(jnp.all(jnp.isfinite(g.astype(jnp.float32))) for g in flat)
    # shapes of grads match params
    assert jax.tree.map(jnp.shape, grads) == jax.tree.map(jnp.shape, params)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_shapes_no_nan(arch, mesh):
    cfg = configs.get_smoke(arch)
    m = Model(cfg, RunConfig(remat=False), mesh=mesh)
    rng = jax.random.PRNGKey(1)
    params = m.init(rng)
    B, S = 2, 16
    batch = make_batch(cfg, rng, B, S)
    logits = jax.jit(m.forward)(params, batch)
    n_prefix = cfg.vision_seq if cfg.vision_embed_dim else 0
    assert logits.shape == (B, S + n_prefix, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits.astype(jnp.float32)))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_decode_step(arch, mesh):
    cfg = configs.get_smoke(arch)
    m = Model(cfg, RunConfig(remat=False), mesh=mesh)
    rng = jax.random.PRNGKey(2)
    params = m.init(rng)
    B = 2
    cache = m.init_cache(B, 32)
    tok = jax.random.randint(rng, (B, 1), 0, cfg.vocab_size)
    enc = (0.01 * jnp.ones((B, cfg.max_source_positions, cfg.d_model),
                           jnp.bfloat16) if cfg.encoder_layers else None)
    step = jax.jit(lambda p, c, t, i: m.decode_step(p, c, t, i, enc_out=enc))
    logits, cache2 = step(params, cache, tok, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits.astype(jnp.float32)))
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


# ----------------------------------------------------------------------
# decode == prefill consistency (fp32 for tight comparison)
# ----------------------------------------------------------------------
CONSISTENCY_ARCHS = ["deepseek-7b", "deepseek-v3-671b", "chatglm3-6b",
                     "mamba2-130m", "jamba-v0.1-52b"]


@pytest.mark.parametrize("arch", CONSISTENCY_ARCHS)
def test_decode_matches_prefill(arch, mesh):
    cfg = configs.get_smoke(arch)
    m = Model(cfg, RunConfig(remat=False), mesh=mesh, dtype=jnp.float32)
    rng = jax.random.PRNGKey(3)
    params = m.init(rng)
    B, S = 2, 8
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    full = jax.jit(m.forward)(params, {"tokens": tokens})

    cache = m.init_cache(B, S)
    step = jax.jit(m.decode_step)
    outs = []
    for t in range(S):
        logits, cache = step(params, cache, tokens[:, t:t + 1],
                             jnp.int32(t))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-2, atol=2e-2)


# ----------------------------------------------------------------------
# structural tests
# ----------------------------------------------------------------------
class TestSegments:
    def test_jamba_pattern(self):
        cfg = configs.get("jamba-v0.1-52b")
        segs = derive_segments(cfg)
        total = sum(len(s.pattern) * s.repeats for s in segs)
        assert total == 32
        # single period-8 segment scanned 4x (compile-size invariant)
        assert len(segs) == 1 and segs[0].repeats == 4
        mixers = [b.mixer for b in segs[0].pattern]
        assert mixers.count("attn") == 1 and mixers.count("mamba") == 7
        # MoE every other layer
        ffns = [b.ffn for s in segs for b in s.pattern for _ in [0]]
        assert ffns.count("moe") == 4

    def test_deepseek_v3_regions(self):
        cfg = configs.get("deepseek-v3-671b")
        segs = derive_segments(cfg)
        assert segs[0].repeats * len(segs[0].pattern) == 3
        assert all(b.ffn == "dense" for b in segs[0].pattern)
        assert segs[1].repeats * len(segs[1].pattern) == 58
        assert all(b.ffn == "moe" for b in segs[1].pattern)

    def test_mamba2_no_mlp(self):
        cfg = configs.get("mamba2-130m")
        segs = derive_segments(cfg)
        assert all(b.mixer == "mamba" and b.ffn == "none"
                   for s in segs for b in s.pattern)

    def test_total_layers(self):
        for name, cfg in configs.ARCHS.items():
            segs = derive_segments(cfg)
            total = sum(len(s.pattern) * s.repeats for s in segs)
            assert total == cfg.n_layers, name


class TestParamCounts:
    """param_counts drives MODEL_FLOPS = 6·N·D in the roofline analysis."""

    def test_deepseek_7b_about_7b(self):
        n = configs.get("deepseek-7b").param_counts()["total"]
        assert 6e9 < n < 8e9, n

    def test_deepseek_v3_total_and_active(self):
        pc = configs.get("deepseek-v3-671b").param_counts()
        assert 5.5e11 < pc["total"] < 7.5e11, pc
        assert 3.0e10 < pc["active"] < 4.5e10, pc

    def test_olmoe_total_and_active(self):
        pc = configs.get("olmoe-1b-7b").param_counts()
        assert 5e9 < pc["total"] < 8e9, pc
        assert 0.8e9 < pc["active"] < 1.7e9, pc

    def test_mamba2_about_130m(self):
        n = configs.get("mamba2-130m").param_counts()["total"]
        assert 0.9e8 < n < 1.8e8, n

    def test_dense_active_equals_total(self):
        for name in ("deepseek-7b", "nemotron-4-15b", "chatglm3-6b",
                     "deepseek-coder-33b"):
            pc = configs.get(name).param_counts()
            assert pc["total"] == pc["active"], name
