"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels compile to Mosaic; on the CPU (the tests) their
bodies run in Pallas interpret mode for correctness validation.  Any
other backend has no kernel path and raises.  ``flash_attention`` is
differentiable through one ``custom_vjp``: the forward kernel saves its
output and per-row log-sum-exp, and the backward runs the dq and dk/dv
kernels on them (``ref.flash_attention_ref`` is the tests' oracle).
q, k, v, the output and the log-sum-exp carry ``checkpoint_name``s
(``qkv``, ``core_out``, ``core_lse``) for the layer scan's remat.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.kernels import flash_attention as _fa
from repro.kernels.moe_gmm import gmm as _gmm
from repro.kernels.ssd import ssd_intra_chunk as _ssd_intra


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for tpu and are interpreted on cpu; "
            f"there is no path for the {backend!r} backend")
    return backend == "cpu"


# ----------------------------------------------------------------------
# flash attention: [B,S,H,hd] layout (model-side convention)
# ----------------------------------------------------------------------
def _merge(x):
    return x.reshape(*x.shape[:2], -1)


def _kernel_args(q, k, causal, scale, blocks):
    bq, bk = blocks or (_fa.block_size(q.shape[1]),
                        _fa.block_size(k.shape[1]))
    return dict(heads=(q.shape[2], k.shape[2]), causal=causal, scale=scale,
                block_q=bq, block_k=bk, interpret=_interpret_default())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, blocks):
    return _flash_fwd(q, k, v, causal, scale, blocks)[0]


def _flash_fwd(q, k, v, causal, scale, blocks):
    # the residuals carry ``checkpoint_name``s the layer scan's remat may
    # keep (``model.REMAT_TIERS``); o is named as the primal output too,
    # or a remat that keeps its residual copy still recomputes it
    q, k, v = (checkpoint_name(t, "qkv") for t in (q, k, v))
    o, lse = _fa.flash_fwd(_merge(q), _merge(k), _merge(v),
                           **_kernel_args(q, k, causal, scale, blocks))
    o = checkpoint_name(o.reshape(*q.shape[:3], v.shape[3]), "core_out")
    lse = checkpoint_name(lse, "core_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, blocks, res, do):
    q, k, v, o, lse = res
    d = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    d = jnp.swapaxes(d, 1, 2)[:, :, None, :]              # [B,H,1,S]
    args = [_merge(q), _merge(k), _merge(v), _merge(do), lse, d]
    kw = _kernel_args(q, k, causal, scale, blocks)
    dq = _fa.flash_dq(*args, **kw)
    dk, dv = _fa.flash_dkv(*args, **kw)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    blocks: Optional[tuple[int, int]] = None) -> jax.Array:
    """q: [B,S,H,hd]; k: [B,T,K,hd]; v: [B,T,K,hdv] → [B,S,H,hdv]
    (GQA-aware).  ``blocks = (block_q, block_k)`` overrides the sizes
    ``flash_attention.block_size`` picks from S and T."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[3])
    return _flash(q, k, v, causal, float(scale), blocks)


# ----------------------------------------------------------------------
# SSD: full chunked layer built on the intra-chunk kernel
# ----------------------------------------------------------------------
def ssd_chunked_pallas(xh: jax.Array, dt: jax.Array, A: jax.Array,
                       Bm: jax.Array, Cm: jax.Array, chunk: int,
                       init_state: Optional[jax.Array] = None):
    """Same contract as models.ssm.ssd_chunked, intra-chunk via Pallas.

    xh: [B,L,H,P], dt: [B,L,H], A: [H], Bm/Cm: [B,L,G,N]."""
    Bsz, L, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert L % chunk == 0
    nc = L // chunk

    # flatten (batch, head) and (batch, group) for the kernel grid
    x_k = xh.reshape(Bsz, nc, chunk, H, P).transpose(0, 3, 1, 2, 4) \
        .reshape(Bsz * H, nc, chunk, P)
    dt_k = dt.reshape(Bsz, nc, chunk, H).transpose(0, 3, 1, 2) \
        .reshape(Bsz * H, nc, chunk)
    A_k = jnp.tile(A, Bsz)
    B_k = Bm.reshape(Bsz, nc, chunk, G, N).transpose(0, 3, 1, 2, 4) \
        .reshape(Bsz * G, nc, chunk, N)
    C_k = Cm.reshape(Bsz, nc, chunk, G, N).transpose(0, 3, 1, 2, 4) \
        .reshape(Bsz * G, nc, chunk, N)

    y_intra, states, cum = _ssd_intra(
        x_k, dt_k, A_k, B_k, C_k, interpret=_interpret_default())

    # inter-chunk recurrence + correction (linear, outside the kernel)
    states = states.reshape(Bsz, H, nc, N, P)
    cum_b = cum.reshape(Bsz, H, nc, chunk)
    chunk_decay = jnp.exp(cum_b[..., -1])                  # [B,H,nc]
    s0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(s, inp):
        dec, st = inp                                      # [B,H], [B,H,N,P]
        s_new = s * dec[..., None, None] + jnp.swapaxes(st, -1, -2)
        return s_new, s

    final, prev = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 2, 0),
                   jnp.moveaxis(states, 2, 0)))
    prev = jnp.moveaxis(prev, 0, 2)                        # [B,H,nc,P,N]

    hpg = H // G
    Ch = jnp.repeat(
        Cm.reshape(Bsz, nc, chunk, G, N)[:, :, :, :, None, :], hpg, axis=4
    ).reshape(Bsz, nc, chunk, H, N)
    decay_from_start = jnp.exp(cum_b).transpose(0, 2, 3, 1)  # [B,nc,Q,H]
    y_inter = jnp.einsum(
        "bcqhn,bhcpn->bcqhp",
        Ch.astype(jnp.float32) * decay_from_start[..., None], prev)

    y_intra = y_intra.reshape(Bsz, H, nc, chunk, P) \
        .transpose(0, 2, 3, 1, 4)                          # [B,nc,Q,H,P]
    y = (y_intra + y_inter).reshape(Bsz, L, H, P)
    return y.astype(xh.dtype), final


# ----------------------------------------------------------------------
# grouped matmul
# ----------------------------------------------------------------------
def grouped_matmul(x: jax.Array, w: jax.Array, **kw) -> jax.Array:
    """x: [E,C,d]; w: [E,d,f] → [E,C,f]."""
    return _gmm(x, w, interpret=_interpret_default(), **kw)
