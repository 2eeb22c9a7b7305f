"""Mamba2 / SSD (state-space duality) blocks: chunked train path +
single-step decode recurrence.

The chunked SSD algorithm (arXiv:2405.21060 §6) splits the sequence into
chunks of length Q: a quadratic attention-like intra-chunk term plus a
linear inter-chunk state recurrence (scanned).  This is the TPU-friendly
form — the intra-chunk einsums are MXU matmuls; ``repro.kernels.ssd``
provides the Pallas kernel for the intra-chunk term.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import dense_init, rmsnorm

Params = dict


def ssm_dims(cfg: ArchConfig) -> dict:
    d_in = cfg.ssm_expand * cfg.d_model
    nh = d_in // cfg.ssm_head_dim
    return {"d_inner": d_in, "n_heads": nh, "head_dim": cfg.ssm_head_dim,
            "n_groups": cfg.ssm_n_groups, "d_state": cfg.ssm_state,
            "conv_dim": d_in + 2 * cfg.ssm_n_groups * cfg.ssm_state}


def ssm_init(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> Params:
    dims = ssm_dims(cfg)
    d, d_in, nh = cfg.d_model, dims["d_inner"], dims["n_heads"]
    G, N, W = dims["n_groups"], dims["d_state"], cfg.ssm_conv
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_in + 2 * G * N + nh     # z, xBC, dt
    return {
        "in_proj": dense_init(ks[0], d, proj_out, dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (W, dims["conv_dim"]),
                                     jnp.float32) / math.sqrt(W)
                   ).astype(dtype),
        "conv_b": jnp.zeros((dims["conv_dim"],), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh).astype(jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm_w": jnp.ones((d_in,), dtype),
        "out_proj": dense_init(ks[3], d_in, d, dtype=dtype),
    }


def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < l <= i} x[..., l]."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


@jax.named_scope("ssm_core")
def ssd_chunked(xh: jax.Array, dt: jax.Array, A: jax.Array,
                Bm: jax.Array, Cm: jax.Array, chunk: int,
                init_state: Optional[jax.Array] = None,
                ) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    xh: [B,L,H,P], dt: [B,L,H] (post-softplus), A: [H] (negative),
    Bm,Cm: [B,L,G,N] with H % G == 0.  Returns (y [B,L,H,P],
    final_state [B,H,P,N]).
    """
    Bsz, L, H, Pd = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk

    f32 = jnp.float32
    xc = xh.reshape(Bsz, nc, chunk, H, Pd).astype(f32)
    dtc = dt.reshape(Bsz, nc, chunk, H).astype(f32)
    Bc = Bm.reshape(Bsz, nc, chunk, G, N).astype(f32)
    Cc = Cm.reshape(Bsz, nc, chunk, G, N).astype(f32)

    dA = dtc * A                                            # [B,nc,Q,H]
    cum = jnp.cumsum(dA, axis=2)                            # [B,nc,Q,H]

    # ---- intra-chunk (quadratic, attention-like) ----------------------
    Lmat = jnp.exp(_segsum(jnp.swapaxes(dA, 2, 3)))         # [B,nc,H,Q,Q]
    CB = jnp.einsum("bcqgn,bckgn->bcgqk", Cc, Bc)           # [B,nc,G,Q,Q]
    CB = jnp.repeat(CB, hpg, axis=2)                        # [B,nc,H,Q,Q]
    xdt = xc * dtc[..., None]                               # [B,nc,Q,H,P]
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", CB * Lmat, xdt)

    # ---- chunk states --------------------------------------------------
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)         # [B,nc,Q,H]
    # broadcast groups -> heads
    Bh = jnp.repeat(Bc[:, :, :, :, None, :], hpg, axis=4
                    ).reshape(Bsz, nc, chunk, H, N)
    Ch = jnp.repeat(Cc[:, :, :, :, None, :], hpg, axis=4
                    ).reshape(Bsz, nc, chunk, H, N)
    states = jnp.einsum("bcqhn,bcqhp->bchpn",
                        Bh * decay_to_end[..., None],
                        xdt)                                # [B,nc,H,P,N]

    # ---- inter-chunk recurrence (scan over chunks) ---------------------
    chunk_decay = jnp.exp(cum[:, :, -1, :])                 # [B,nc,H]
    s0 = (jnp.zeros((Bsz, H, Pd, N), f32) if init_state is None
          else init_state.astype(f32))

    def step(s, inp):
        dec, st = inp                                       # [B,H], [B,H,P,N]
        s_new = s * dec[..., None, None] + st
        return s_new, s

    final, prev_states = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(states, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)           # [B,nc,H,P,N]

    decay_from_start = jnp.exp(cum)                         # [B,nc,Q,H]
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp",
                         Ch * decay_from_start[..., None], prev_states)

    y = (y_intra + y_inter).reshape(Bsz, L, H, Pd)
    return y.astype(xh.dtype), final


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv1d.  x: [B,L,C]; w: [W,C].  Returns (y, new
    state [B,W-1,C]) — state carries the last W-1 inputs for decode."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                  # [B,L+W-1,C]
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(W))
    new_state = xp[:, -(W - 1):, :]
    return jax.nn.silu(y + b), new_state


def ssm_apply(p: Params, x: jax.Array, cfg: ArchConfig, *,
              cache: Optional[Params] = None,
              chunk: Optional[int] = None):
    """Mamba2 block.  Train/prefill: cache None, x [B,L,d].
    Decode: x [B,1,d], cache {"conv": [B,W-1,C], "state": [B,H,P,N]}.
    Returns (y [B,L,d], new_cache)."""
    dims = ssm_dims(cfg)
    B_, L, d = x.shape
    d_in, nh, hd = dims["d_inner"], dims["n_heads"], dims["head_dim"]
    G, N = dims["n_groups"], dims["d_state"]

    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :d_in]
    xBC = zxbcdt[..., d_in:d_in + dims["conv_dim"]]
    dt_raw = zxbcdt[..., -nh:]

    conv_state = cache["conv"] if cache is not None else None
    xBC, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"], conv_state)

    xs = xBC[..., :d_in].reshape(B_, L, nh, hd)
    Bm = xBC[..., d_in:d_in + G * N].reshape(B_, L, G, N)
    Cm = xBC[..., d_in + G * N:].reshape(B_, L, G, N)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])                                # [H], negative

    if cache is None:
        y, final = ssd_chunked(xs, dt, A, Bm, Cm,
                               min(chunk or cfg.ssm_chunk, L))
        new_cache = None
    else:
        # single-step recurrence: S = exp(dt*A) S + dt * B ⊗ x ; y = C·S
        s = cache["state"].astype(jnp.float32)              # [B,H,P,N]
        hpg = nh // G
        Bh = jnp.repeat(Bm[:, 0, :, None, :], hpg, axis=2
                        ).reshape(B_, nh, N).astype(jnp.float32)
        Ch = jnp.repeat(Cm[:, 0, :, None, :], hpg, axis=2
                        ).reshape(B_, nh, N).astype(jnp.float32)
        dt0 = dt[:, 0]                                      # [B,H]
        xe = xs[:, 0].astype(jnp.float32)                   # [B,H,P]
        dec = jnp.exp(dt0 * A)                              # [B,H]
        s = s * dec[..., None, None] \
            + jnp.einsum("bhn,bhp,bh->bhpn", Bh, xe, dt0)
        y = jnp.einsum("bhn,bhpn->bhp", Ch, s)[:, None]     # [B,1,H,P]
        y = y.astype(x.dtype)
        final = s
        new_cache = {"conv": new_conv, "state": final}

    y = y + (p["D"].astype(jnp.float32)[:, None]
             * xs.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(B_, L, d_in)
    y = rmsnorm(p["norm_w"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ p["out_proj"]
    if cache is None:
        return out, None
    return out, new_cache


def ssm_cache_init(cfg: ArchConfig, batch: int, dtype=jnp.bfloat16) -> Params:
    dims = ssm_dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, dims["conv_dim"]),
                          dtype),
        "state": jnp.zeros((batch, dims["n_heads"], dims["head_dim"],
                            dims["d_state"]), jnp.float32),
    }
