"""Flash attention as Pallas TPU kernels: forward, dq and dk/dv.

Layout: q is ``[B,S,H·hd]`` and k, v are ``[B,T,K·hd]`` — the model's
``[B,S,H,hd]`` with the last two dims merged, so no transpose surrounds
the kernels.  A block is ``(rows, hd)`` on the last two dims and the
``index_map`` picks the head; on a TPU ``hd`` is a multiple of 128
(Mosaic's lane tiling).  GQA is structural: query head ``h`` reads kv
head ``h // (H // K)``.

Forward, grid ``(B, H, S/bq)``: one q block meets the whole K/V panel of
its kv head, held in VMEM (the panel's block index does not change along
the q axis, so it is fetched once per head).  A loop over k blocks keeps
the online softmax (running max, denominator, accumulator) in f32 and
stops at the diagonal; blocks wholly below it skip the mask.  The MXU
gets the inputs' dtype (bf16 in training) with f32 accumulation, and P
enters the PV product in that dtype.  The score tile never leaves VMEM.
Besides the output it emits the per-row log-sum-exp, f32 ``[B,H,1,S]``.

Backward: ``D = rowsum(dO∘O)`` comes from XLA.  The dq kernel, grid
``(B, H, S/bq)``, loops over k blocks up to the diagonal; the dk/dv
kernel, grid ``(B, K, T/bk, G)``, loops over q blocks from the diagonal
on, and over the ``G`` query heads of its kv head (the innermost grid
axis, summed in f32 scratch).  Both rebuild ``P = exp(s − lse)`` from q,
k and the saved log-sum-exp; the dk/dv kernel works on transposed tiles
(k rows, q lanes), so every product is a plain or rhs-transposed matmul.

The panels bound the sequence: ``fits`` says whether one does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_VMEM_LIMIT = 64 * 2**20
_PANEL_BUDGET = 32 * 2**20               # two double-buffered panels


def block_size(n: int) -> int:
    """The largest of 512/256/128 that divides ``n`` (``n`` itself when
    none does: a block may span a whole axis)."""
    for b in (512, 256, 128):
        if n % b == 0:
            return b
    return n


def fits(seq: int, head_dim: int, itemsize: int) -> bool:
    """Whether the compiled kernels take this sequence and head size."""
    return (head_dim % 128 == 0 and seq % 128 == 0
            and 4 * seq * head_dim * itemsize <= _PANEL_BUDGET)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _causal_mask(s, row0, col0, *, k_rows: bool):
    """Keep key ≤ query; rows and columns start at ``row0``/``col0``."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = rows <= cols if k_rows else cols <= rows
    return jnp.where(keep, s, NEG_INF)


def _k_range(i, block_q, block_k, n_kb, causal):
    """k blocks for q block ``i``: ``[0, full)`` lie wholly at or below
    the diagonal, ``[full, hi)`` straddle it."""
    if not causal:
        return n_kb, n_kb
    full = (i * block_q + 1) // block_k
    hi = ((i + 1) * block_q - 1) // block_k + 1
    return full, hi


def _q_range(j, block_q, block_k, n_qb, causal):
    """q blocks for k block ``j``: ``[lo, full)`` straddle the diagonal,
    ``[full, n_qb)`` lie wholly below it."""
    if not causal:
        return 0, 0
    lo = (j * block_k) // block_q
    full = ((j + 1) * block_k - 1 + block_q - 1) // block_q
    return lo, jnp.minimum(full, n_qb)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k):
    block_q = q_ref.shape[0]
    n_kb = k_ref.shape[0] // block_k
    q0 = pl.program_id(2) * block_q
    q = q_ref[...]

    def body(masked, j, carry):
        acc, m, l = carry
        k0 = pl.multiple_of(j * block_k, block_k)
        k = k_ref[pl.ds(k0, block_k), :]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal_mask(s, q0, k0, k_rows=False)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[pl.ds(k0, block_k), :]
        acc = alpha * acc + jax.lax.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
        return acc, m_new, l

    full, hi = _k_range(pl.program_id(2), block_q, block_k, n_kb, causal)
    carry = (jnp.zeros((block_q, v_ref.shape[1]), jnp.float32),
             jnp.full((block_q, 1), NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32))
    carry = jax.lax.fori_loop(0, full, functools.partial(body, False), carry)
    acc, m, l = jax.lax.fori_loop(full, hi, functools.partial(body, True),
                                  carry)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    # the [bq,1] column becomes the lane-major [1,bq] row the backward
    # reads: transpose a lane-broadcast tile, keep one row
    lse = jnp.broadcast_to(m + jnp.log(l), (block_q, 128))
    lse_ref[...] = lse.T[:1]


def flash_fwd(q, k, v, *, heads: tuple[int, int], causal: bool,
              scale: float, block_q: int, block_k: int, interpret: bool):
    """q: [B,S,H·hd]; k: [B,T,K·hd]; v: [B,T,K·hdv], ``heads = (H, K)``.
    Returns (o [B,S,H·hdv] in q's dtype, lse [B,H,1,S] f32)."""
    H, K = heads
    B, S, _ = q.shape
    T = k.shape[1]
    hd, hdv = q.shape[2] // H, v.shape[2] // K
    G = H // K
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k)
    sq = pl.squeezed
    return pl.pallas_call(
        kernel,
        grid=(B, H, S // block_q),
        in_specs=[
            pl.BlockSpec((sq, block_q, hd), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((sq, T, hd), lambda b, h, i: (b, 0, h // G)),
            pl.BlockSpec((sq, T, hdv), lambda b, h, i: (b, 0, h // G)),
        ],
        out_specs=[
            pl.BlockSpec((sq, block_q, hdv), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((sq, sq, 1, block_q), lambda b, h, i: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * hdv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
    )(q, k, v)


# ----------------------------------------------------------------------
# backward: dq
# ----------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, *,
               scale, causal, block_k):
    block_q = q_ref.shape[0]
    n_kb = k_ref.shape[0] // block_k
    q0 = pl.program_id(2) * block_q
    q, do = q_ref[...], do_ref[...]
    lse = jnp.expand_dims(lse_ref[0], -1)                 # [bq,1]
    d = jnp.expand_dims(d_ref[0], -1)

    def body(masked, j, acc):
        k0 = pl.multiple_of(j * block_k, block_k)
        k = k_ref[pl.ds(k0, block_k), :]
        v = v_ref[pl.ds(k0, block_k), :]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal_mask(s, q0, k0, k_rows=False)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - d)
        return acc + jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    full, hi = _k_range(pl.program_id(2), block_q, block_k, n_kb, causal)
    acc = jnp.zeros((block_q, q_ref.shape[1]), jnp.float32)
    acc = jax.lax.fori_loop(0, full, functools.partial(body, False), acc)
    acc = jax.lax.fori_loop(full, hi, functools.partial(body, True), acc)
    dq_ref[...] = (acc * scale).astype(dq_ref.dtype)


def flash_dq(q, k, v, do, lse, d, *, heads, causal, scale, block_q,
             block_k, interpret):
    """dq [B,S,H·hd] from the forward's inputs, dO, lse and D
    (``rowsum(dO∘O)``, f32 [B,H,1,S])."""
    H, K = heads
    B, S, _ = q.shape
    T = k.shape[1]
    hd, hdv = q.shape[2] // H, v.shape[2] // K
    G = H // K
    kernel = functools.partial(_dq_kernel, scale=scale, causal=causal,
                               block_k=block_k)
    sq = pl.squeezed
    row = pl.BlockSpec((sq, sq, 1, block_q), lambda b, h, i: (b, h, 0, i))
    return pl.pallas_call(
        kernel,
        grid=(B, H, S // block_q),
        in_specs=[
            pl.BlockSpec((sq, block_q, hd), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((sq, T, hd), lambda b, h, i: (b, 0, h // G)),
            pl.BlockSpec((sq, T, hdv), lambda b, h, i: (b, 0, h // G)),
            pl.BlockSpec((sq, block_q, hdv), lambda b, h, i: (b, i, h)),
            row, row,
        ],
        out_specs=pl.BlockSpec((sq, block_q, hd), lambda b, h, i: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
    )(q, k, v, do, lse, d)


# ----------------------------------------------------------------------
# backward: dk, dv
# ----------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale, causal, block_q):
    block_k = k_ref.shape[0]
    n_qb = q_ref.shape[0] // block_q
    j, g = pl.program_id(2), pl.program_id(3)
    k0 = j * block_k
    k, v = k_ref[...], v_ref[...]

    @pl.when(g == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked, i, carry):
        dk, dv = carry
        q0 = pl.multiple_of(i * block_q, block_q)
        q = q_ref[pl.ds(q0, block_q), :]
        do = do_ref[pl.ds(q0, block_q), :]
        lse = lse_ref[:, pl.ds(q0, block_q)]              # [1,bq]
        d = d_ref[:, pl.ds(q0, block_q)]
        st = jax.lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            st = _causal_mask(st, k0, q0, k_rows=True)
        pt = jnp.exp(st - lse)                            # [bk,bq]
        dv = dv + jax.lax.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - d)
        dk = dk + jax.lax.dot(dst.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)
        return dk, dv

    lo, full = _q_range(j, block_q, block_k, n_qb, causal)
    carry = (dk_acc[...], dv_acc[...])
    carry = jax.lax.fori_loop(lo, full, functools.partial(body, True), carry)
    dk, dv = jax.lax.fori_loop(full, n_qb,
                               functools.partial(body, False), carry)
    dk_acc[...] = dk
    dv_acc[...] = dv

    @pl.when(g == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def flash_dkv(q, k, v, do, lse, d, *, heads, causal, scale, block_q,
              block_k, interpret):
    """(dk [B,T,K·hd], dv [B,T,K·hdv]); arguments as for ``flash_dq``."""
    H, K = heads
    B, S, _ = q.shape
    T = k.shape[1]
    hd, hdv = q.shape[2] // H, v.shape[2] // K
    G = H // K
    kernel = functools.partial(_dkv_kernel, scale=scale, causal=causal,
                               block_q=block_q)
    sq = pl.squeezed
    row = pl.BlockSpec((sq, sq, 1, S), lambda b, c, j, g: (b, c * G + g, 0, 0))
    kv = lambda w: pl.BlockSpec((sq, block_k, w),                # noqa: E731
                                lambda b, c, j, g: (b, j, c))
    panel = lambda w: pl.BlockSpec((sq, S, w),                   # noqa: E731
                                   lambda b, c, j, g: (b, 0, c * G + g))
    return pl.pallas_call(
        kernel,
        grid=(B, K, T // block_k, G),
        in_specs=[panel(hd), kv(hd), kv(hdv), panel(hdv), row, row],
        out_specs=[kv(hd), kv(hdv)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hdv), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(q, k, v, do, lse, d)
