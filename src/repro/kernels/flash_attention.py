"""Flash attention (forward) as a Pallas TPU kernel.

TPU adaptation of the blockwise-softmax algorithm: q blocks of
``block_q`` rows are staged into VMEM via BlockSpec; the kernel streams
k/v in ``block_k`` slices from the VMEM-resident per-(batch,head) K/V
panels and maintains the running (max, denominator, accumulator) online
softmax in fp32 VREGs.  Causal queries skip entire KV blocks beyond the
diagonal (the loop bound depends on the q-block index).

GQA is handled *structurally*: the k/v BlockSpec index_map sends query
head ``h`` to kv head ``h // (H // K)``, so grouped heads share the same
VMEM panel without materializing repeated k/v.

VMEM budget: the per-(b,h) K and V panels are (S, hd) each —
``2·S·hd·bytes ≤ ~4 MiB`` holds for the training shapes this kernel
serves (S ≤ 8k at hd=128 bf16).  Longer sequences use the XLA path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                  causal: bool, block_k: int):
    block_q, hd = q_ref.shape[2], q_ref.shape[3]
    seq_k = k_ref.shape[2]
    q_idx = pl.program_id(2)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, hd]

    n_kb = seq_k // block_k
    if causal:
        hi = jnp.minimum(
            (q_idx * block_q + block_q + block_k - 1) // block_k, n_kb)
    else:
        hi = n_kb

    def body(i, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.dslice(i * block_k, block_k), :] \
            .astype(jnp.float32)                         # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = q_idx * block_q + jax.lax.iota(jnp.int32, block_q)
            kpos = i * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0, 0, pl.dslice(i * block_k, block_k), :] \
            .astype(jnp.float32)
        acc = acc * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, v_ref.shape[3]), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, scale: float | None = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool) -> jax.Array:
    """q: [B,H,S,hd]; k,v: [B,K,T,hd] with H % K == 0.  Returns [B,H,S,hd']."""
    B, H, S, hd = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)

    grid = (B, H, S // block_q)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, T, hd), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, T, v.shape[3]),
                         lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, v.shape[3]),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, v.shape[3]), q.dtype),
        interpret=interpret,
    )(q, k, v)
