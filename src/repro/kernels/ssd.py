"""Mamba2 SSD intra-chunk kernel (Pallas TPU).

The chunked SSD algorithm's dominant cost is the intra-chunk quadratic
term: per (batch, head, chunk), with chunk length Q, head dim P and state
dim N —

    cum   = cumsum(dt·A)                          [Q]
    L     = exp(segsum(dt·A)) (lower-triangular)  [Q,Q]
    y     = ((C Bᵀ) ∘ L) (x·dt)                   [Q,P]
    state = (B · exp(cum[-1]−cum))ᵀ (x·dt)        [N,P]  (chunk's state
                                                   contribution)

The whole chunk fits VMEM (Q≤256, P=64, N≤128 ⇒ < 1 MiB fp32), so one
grid step = one (b, h, chunk) tile; group→head broadcast of B/C happens
in the BlockSpec index_map (no repeat materialized).  The per-chunk
cumsum is a [BH,nc,Q] pass done by XLA before the call: the kernel takes
it as a column [Q,1] and a row [1,Q] (and dt as a column), so every
block's last two dims are whole or (8,128)-aligned as Mosaic requires
and ``segsum`` is one broadcast subtraction.  The linear inter-chunk
recurrence stays outside (a length-nc ``lax.scan`` on [B,H,P,N] —
negligible FLOPs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref,
                y_ref, state_ref):
    Q = x_ref.shape[2]
    f32 = jnp.float32

    x = x_ref[0, 0].astype(f32)                    # [Q,P]
    dt = dt_ref[0, 0]                              # [Q,1]
    cum_c = cumc_ref[0, 0]                         # [Q,1]
    cum_r = cumr_ref[0, 0]                         # [1,Q]
    Bm = b_ref[0, 0].astype(f32)                   # [Q,N]
    Cm = c_ref[0, 0].astype(f32)                   # [Q,N]

    seg = cum_c - cum_r                            # [Q,Q]
    tril = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    Lmat = jnp.where(tril, jnp.exp(jnp.where(tril, seg, 0.0)), 0.0)

    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)  # [Q,Q]
    xdt = x * dt                                   # [Q,P]
    y = jax.lax.dot(CB * Lmat, xdt, preferred_element_type=f32)

    decay_end = jnp.exp(cum_c[Q - 1:Q, :] - cum_c)  # [Q,1]
    state = jax.lax.dot_general(Bm * decay_end, xdt,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=f32)  # [N,P]

    y_ref[0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0] = state.astype(state_ref.dtype)


def ssd_intra_chunk(x: jax.Array, dt: jax.Array, A: jax.Array,
                    Bm: jax.Array, Cm: jax.Array, *,
                    interpret: bool):
    """x: [BH, nc, Q, P] (batch·heads flattened), dt: [BH, nc, Q],
    A: [BH], Bm/Cm: [BG, nc, Q, N] where BG = BH // heads_per_group
    collapsed the same way.  Group broadcast is expressed through the
    index_map using ``hpg`` = BH // BG.

    Returns (y_intra [BH,nc,Q,P], states [BH,nc,N,P], cum [BH,nc,Q]).
    """
    BH, nc, Q, P = x.shape
    BG, N = Bm.shape[0], Bm.shape[3]
    hpg = BH // BG

    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum(dt * A.astype(jnp.float32)[:, None, None], axis=-1)
    col = lambda a: a[..., :, None]                # [BH,nc,Q,1]
    row = lambda a: a[..., None, :]                # [BH,nc,1,Q]

    y, states = pl.pallas_call(
        _ssd_kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda h, c: (h // hpg, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda h, c: (h // hpg, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda h, c: (h, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(x, col(dt), col(cum), row(cum), Bm, Cm)
    return y, states, cum
