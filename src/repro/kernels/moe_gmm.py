"""Grouped (per-expert) matmul kernel for MoE expert FFNs (Pallas TPU).

Computes ``out[e] = x[e] @ w[e]`` for E experts over capacity-gathered
token blocks — the compute core of the EP MoE layer after dispatch.
Grid = (E, C/block_c, f/block_f); each step stages an (block_c, d) token
tile and a (d, block_f) weight tile into VMEM and runs one MXU matmul
with fp32 accumulation, contracting d in ``block_d`` slices to bound the
VMEM working set:

    VMEM ≈ block_c·block_d + block_d·block_f + block_c·block_f  (fp32 acc)

which stays < 2 MiB at the default 128/512/128 tiling even for d=7168.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gmm_kernel(x_ref, w_ref, o_ref, *, block_d: int):
    C, d = x_ref.shape[1], x_ref.shape[2]
    f = w_ref.shape[2]
    nd = d // block_d

    def body(i, acc):
        xb = x_ref[0, :, pl.dslice(i * block_d, block_d)]
        wb = w_ref[0, pl.dslice(i * block_d, block_d), :]
        return acc + jax.lax.dot(xb, wb,
                                 preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(
        0, nd, body, jnp.zeros((C, f), jnp.float32))
    o_ref[0] = acc.astype(o_ref.dtype)


def gmm(x: jax.Array, w: jax.Array, *, block_c: int = 128,
        block_f: int = 128, block_d: int = 512,
        interpret: bool) -> jax.Array:
    """x: [E, C, d]; w: [E, d, f] → [E, C, f]."""
    E, C, d = x.shape
    f = w.shape[2]
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert C % block_c == 0 and f % block_f == 0 and d % block_d == 0

    grid = (E, C // block_c, f // block_f)
    kernel = functools.partial(_gmm_kernel, block_d=block_d)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, d), lambda e, i, j: (e, i, 0)),
            pl.BlockSpec((1, d, block_f), lambda e, i, j: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, i, j: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        interpret=interpret,
    )(x, w)
