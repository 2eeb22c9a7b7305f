"""Pallas kernel validation: shape/dtype sweeps vs the jnp oracles
(interpret mode executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("S,hd,H,K", [
        (128, 32, 2, 2),    # MHA
        (128, 64, 4, 2),    # GQA 2:1
        (256, 32, 4, 1),    # MQA
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, S, hd, H, K, causal, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        B = 2
        q = rand(ks[0], (B, S, H, hd), dtype)
        k = rand(ks[1], (B, S, K, hd), dtype)
        v = rand(ks[2], (B, S, K, hd), dtype)
        out = ops.flash_attention(q, k, v, causal=causal)
        want = ref.flash_attention_ref(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=causal)
        want = jnp.swapaxes(want, 1, 2)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **TOL[dtype])

    def test_block_size_invariance(self):
        """Result must not depend on the tiling."""
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = rand(ks[0], (1, 256, 2, 32), jnp.float32)
        k = rand(ks[1], (1, 256, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 256, 2, 32), jnp.float32)
        a = ops.flash_attention(q, k, v, blocks=(64, 64))
        b = ops.flash_attention(q, k, v, blocks=(128, 32))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_gradient_flows(self):
        """custom_vjp: kernel fwd + dq and dk/dv kernels."""
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = rand(ks[0], (1, 128, 2, 32), jnp.float32)
        k = rand(ks[1], (1, 128, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 128, 2, 32), jnp.float32)

        def loss_kernel(q, k, v):
            return jnp.sum(ops.flash_attention(q, k, v) ** 2)

        def loss_ref(q, k, v):
            o = ref.flash_attention_ref(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2))
            return jnp.sum(jnp.swapaxes(o, 1, 2) ** 2)

        g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


def _bhsd(x):
    return jnp.swapaxes(x, 1, 2)


def _ref_bshd(q, k, v, causal=True):
    """The oracle in f32 on [B,S,H,hd] inputs."""
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    return _bhsd(ref.flash_attention_ref(*map(_bhsd, f32), causal=causal))


def _lse_ref(q, k, causal=True):
    """Per-row log-sum-exp of the oracle's scaled, masked f32 scores."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    kr = jnp.repeat(k.astype(jnp.float32), H // K, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), kr,
                   precision="highest") / np.sqrt(hd)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    return jax.nn.logsumexp(s, axis=-1)                   # [B,H,S]


# heads (H, K), sequence and (block_q, block_k): S spans several blocks,
# and unequal blocks leave diagonal blocks partly masked
KERNEL_CASES = [
    pytest.param((4, 4), 256, (64, 64), id="mha-64x64"),
    pytest.param((8, 2), 384, (128, 64), id="gqa4-128x64"),
    pytest.param((4, 1), 256, (64, 128), id="mqa-64x128"),
    pytest.param((4, 4), 512, (128, 256), id="mha-128x256"),
]
KERNEL_TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
              jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestFlashKernels:
    """The forward, dq and dk/dv kernels against the oracle."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("heads,S,blocks", KERNEL_CASES)
    def test_forward_and_lse_match_ref(self, heads, S, blocks, dtype):
        from repro.kernels.flash_attention import flash_fwd
        (H, K), hd = heads, 32
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q = rand(ks[0], (2, S, H, hd), dtype)
        k = rand(ks[1], (2, S, K, hd), dtype)
        v = rand(ks[2], (2, S, K, hd), dtype)
        o, lse = flash_fwd(
            q.reshape(2, S, H * hd), k.reshape(2, S, K * hd),
            v.reshape(2, S, K * hd), heads=heads, causal=True,
            scale=hd ** -0.5, block_q=blocks[0], block_k=blocks[1],
            interpret=True)
        assert o.dtype == dtype and lse.shape == (2, H, 1, S)
        np.testing.assert_allclose(
            np.asarray(o.reshape(2, S, H, hd), np.float32),
            np.asarray(_ref_bshd(q, k, v)), **KERNEL_TOL[dtype])
        np.testing.assert_allclose(np.asarray(lse[:, :, 0]),
                                   np.asarray(_lse_ref(q, k)),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("heads,S,blocks", KERNEL_CASES)
    def test_gradients_match_ref(self, heads, S, blocks, dtype):
        """dq, dk, dv through ``ops.flash_attention`` against ``jax.vjp``
        of the oracle, at the scale of each gradient."""
        (H, K), hd = heads, 32
        ks = jax.random.split(jax.random.PRNGKey(9), 4)
        q = rand(ks[0], (1, S, H, hd), dtype)
        k = rand(ks[1], (1, S, K, hd), dtype)
        v = rand(ks[2], (1, S, K, hd), dtype)
        g = rand(ks[3], (1, S, H, hd), dtype)
        _, vjp = jax.vjp(lambda *a: ops.flash_attention(*a, blocks=blocks),
                         q, k, v)
        _, vjp_ref = jax.vjp(_ref_bshd, q, k, v)
        for got, want in zip(vjp(g), vjp_ref(g.astype(jnp.float32))):
            assert got.dtype == dtype
            got, want = np.asarray(got, np.float32), np.asarray(want)
            scale = np.abs(want).max()
            tol = KERNEL_TOL[dtype]
            np.testing.assert_allclose(got / scale, want / scale,
                                       rtol=tol["rtol"], atol=tol["atol"])


class TestSSD:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("Q,P,N,G,H", [
        (16, 16, 8, 1, 2),
        (32, 32, 16, 2, 4),
    ])
    def test_intra_chunk_matches_ref(self, Q, P, N, G, H, dtype):
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        BH, BG, nc = 2 * H, 2 * G, 3
        x = rand(ks[0], (BH, nc, Q, P), dtype)
        dt = jax.nn.softplus(rand(ks[1], (BH, nc, Q), jnp.float32))
        A = -jnp.abs(rand(ks[2], (BH,), jnp.float32)) - 0.1
        Bm = rand(ks[3], (BG, nc, Q, N), dtype)
        Cm = rand(ks[0], (BG, nc, Q, N), dtype)
        from repro.kernels.ssd import ssd_intra_chunk
        y, st, cum = ssd_intra_chunk(x, dt, A, Bm, Cm, interpret=True)
        yr, str_, cumr = ref.ssd_intra_chunk_ref(x, dt, A, Bm, Cm)
        tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
            else dict(rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), **tol)
        np.testing.assert_allclose(np.asarray(st), np.asarray(str_), **tol)
        np.testing.assert_allclose(np.asarray(cum), np.asarray(cumr),
                                   rtol=1e-5, atol=1e-5)

    def test_full_chunked_layer_matches_sequential(self):
        """State-space duality: chunked(kernel) == sequential recurrence."""
        ks = jax.random.split(jax.random.PRNGKey(4), 5)
        B, L, H, P, G, N, chunk = 2, 64, 4, 16, 2, 8, 16
        x = rand(ks[0], (B, L, H, P), jnp.float32)
        dt = jax.nn.softplus(rand(ks[1], (B, L, H), jnp.float32))
        A = -jnp.abs(rand(ks[2], (H,), jnp.float32)) - 0.1
        Bm = rand(ks[3], (B, L, G, N), jnp.float32)
        Cm = rand(ks[4], (B, L, G, N), jnp.float32)
        y, final = ops.ssd_chunked_pallas(x, dt, A, Bm, Cm, chunk)
        yr, finalr = ref.ssd_sequential_ref(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(final), np.asarray(finalr),
                                   rtol=1e-3, atol=1e-3)

    def test_jnp_chunked_model_path_matches_sequential(self):
        """models.ssm.ssd_chunked (the XLA train path) vs the recurrence."""
        from repro.models.ssm import ssd_chunked
        ks = jax.random.split(jax.random.PRNGKey(5), 5)
        B, L, H, P, G, N, chunk = 2, 64, 2, 8, 1, 8, 16
        x = rand(ks[0], (B, L, H, P), jnp.float32)
        dt = jax.nn.softplus(rand(ks[1], (B, L, H), jnp.float32))
        A = -jnp.abs(rand(ks[2], (H,), jnp.float32)) - 0.1
        Bm = rand(ks[3], (B, L, G, N), jnp.float32)
        Cm = rand(ks[4], (B, L, G, N), jnp.float32)
        y, final = ssd_chunked(x, dt, A, Bm, Cm, chunk)
        yr, finalr = ref.ssd_sequential_ref(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(final), np.asarray(finalr),
                                   rtol=1e-3, atol=1e-3)


class TestBackend:
    @pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                                   ("tpu", False)])
    def test_interpret_only_on_cpu(self, monkeypatch, backend, interpret):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert ops._interpret_default() is interpret

    def test_other_backends_have_no_kernel_path(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret_default()


class TestGMM:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("E,C,d,f", [
        (2, 16, 32, 32),
        (4, 64, 128, 64),
        (3, 32, 96, 48),
    ])
    def test_matches_ref(self, E, C, d, f, dtype):
        ks = jax.random.split(jax.random.PRNGKey(6), 2)
        x = rand(ks[0], (E, C, d), dtype)
        w = rand(ks[1], (E, d, f), dtype)
        out = ops.grouped_matmul(x, w, block_c=16, block_f=16, block_d=32)
        want = ref.gmm_ref(x, w)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **TOL[dtype])

    def test_tiling_invariance(self):
        ks = jax.random.split(jax.random.PRNGKey(7), 2)
        x = rand(ks[0], (2, 64, 64), jnp.float32)
        w = rand(ks[1], (2, 64, 32), jnp.float32)
        a = ops.grouped_matmul(x, w, block_c=64, block_f=32, block_d=64)
        b = ops.grouped_matmul(x, w, block_c=16, block_f=16, block_d=16)
        # summation order differs across block_d -> fp32 noise only
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
