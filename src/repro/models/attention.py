"""Attention: GQA (grouped-query) and MLA (multi-head latent), train +
decode (KV cache) + cross-attention.

The core dot-product attention (``sdpa``) runs one of three paths — the
Pallas flash kernels, ``_xla_flash`` or an einsum — chosen per call from
``RunConfig.attn_impl``:

- ``"auto"`` (the default) resolves by what the call shows: on a TPU,
  causal self-attention (``S == T``, no cache length, positions absent
  or 1-D) with one head dim for q, k and v that the kernels take
  (``flash_attention.fits``) runs the Pallas flash kernels; otherwise
  ``"xla_flash"`` where its conditions hold; otherwise ``"xla"``.  MLA's
  192/128 heads, 64-wide heads, decode and cross-attention keep XLA.
- ``"pallas"`` forces the kernels (forward and backward in
  ``repro.kernels``; interpreted on the CPU) for causal self-attention.
- ``"xla_flash"`` forces ``_xla_flash``: a scan over query blocks.
- ``"xla"`` forces the einsum (memory-efficient GQA grouping, fp32
  softmax), the path of decode and cross-attention.

``DISPATCH`` tallies, at trace time, which path each ``sdpa`` call took.

MLA decode uses the *absorbed* formulation: attention runs in the
compressed-KV latent space so the cache holds only kv_lora+rope dims per
token (DeepSeek-V3's memory win).
"""
from __future__ import annotations

import collections
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import apply_rope, dense_init, rmsnorm, rmsnorm_init

Params = dict
NEG_INF = -1e30


# ----------------------------------------------------------------------
# core scaled-dot-product attention with GQA grouping
# ----------------------------------------------------------------------
def _xla_flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
               causal: bool, scale: float, block_q: int = 256) -> jax.Array:
    """Blockwise attention in pure XLA: ``lax.scan`` over query blocks with
    a rematerialized body keeps live memory O(block·T) instead of O(S²) in
    both fwd and bwd — the same asymptotics the Pallas kernel has on TPU,
    so dry-run memory analysis is representative.
    q: [B,S,H,hd]; k,v: [B,T,K,hd]."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    nb = S // block_q
    qb = q.reshape(B, nb, block_q, K, G, hd)
    qb = jnp.moveaxis(qb, 1, 0)                   # [nb,B,blk,K,G,hd]

    @jax.checkpoint
    def body(_, args):
        qi, i = args
        s = jnp.einsum("bskgh,btkh->bkgst", qi, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * block_q + jnp.arange(block_q)
            mask = qpos[:, None] >= jnp.arange(T)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgst,btkh->bskgh", p, v)
        return None, o

    _, ob = jax.lax.scan(body, None, (qb, jnp.arange(nb)))
    return ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, v.shape[-1])


DISPATCH: collections.Counter = collections.Counter()


def _path(impl, q, k, v, *, causal, q_positions, k_valid_len) -> str:
    """Which implementation a call takes: "kernel", "xla_flash" or
    "einsum" (module docstring)."""
    S, T = q.shape[1], k.shape[1]
    self_attn = S == T and k_valid_len is None
    flat_pos = q_positions is None or q_positions.ndim == 1
    if impl == "auto":
        from repro.kernels import flash_attention as _fa
        hd = q.shape[3]
        if (jax.default_backend() == "tpu" and causal and self_attn
                and flat_pos and k.shape[3] == v.shape[3] == hd
                and _fa.fits(S, hd, q.dtype.itemsize)):
            return "kernel"
        impl = "xla_flash"
    if impl == "pallas" and causal and self_attn:
        return "kernel"
    if impl == "xla_flash" and self_attn and flat_pos and S % 256 == 0:
        return "xla_flash"
    return "einsum"


@jax.named_scope("attention_core")
def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *,
         causal: bool,
         q_positions: Optional[jax.Array] = None,
         k_valid_len: Optional[jax.Array] = None,
         impl: str = "xla",
         scale: Optional[float] = None) -> jax.Array:
    """q: [B,S,H,hd]; k,v: [B,T,K,hd] with H % K == 0.  Returns [B,S,H,hd].

    ``q_positions`` ([S] or [B,S]) anchors causal masking for decode;
    ``k_valid_len`` masks cache slots beyond the current length.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    path = _path(impl, q, k, v, causal=causal, q_positions=q_positions,
                 k_valid_len=k_valid_len)
    DISPATCH[path] += 1
    if path == "kernel":
        from repro.kernels import ops as _kops
        return _kops.flash_attention(q, k, v, causal=True, scale=scale)
    if path == "xla_flash":
        return _xla_flash(q, k, v, causal=causal, scale=scale)

    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(T)
    mask = None
    if causal:
        q_pos = (jnp.arange(S) if q_positions is None else q_positions)
        if q_pos.ndim == 1:
            m = q_pos[:, None] >= k_pos[None, :]              # [S,T]
            mask = m[None, None, None]
        else:
            m = q_pos[:, :, None] >= k_pos[None, None, :]     # [B,S,T]
            mask = m[:, None, None]
    if k_valid_len is not None:
        lm = k_pos[None, :] < k_valid_len[:, None]            # [B,T]
        lm = lm[:, None, None, None]                          # [B,1,1,1,T]
        mask = lm if mask is None else jnp.logical_and(mask, lm)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, H, v.shape[-1])   # v dim may differ (MLA)


# ----------------------------------------------------------------------
# GQA block
# ----------------------------------------------------------------------
def gqa_init(key, cfg: ArchConfig, *, cross: bool = False,
             dtype=jnp.bfloat16) -> Params:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, H * hd, dtype=dtype),
        "wk": dense_init(ks[1], d, K * hd, dtype=dtype),
        "wv": dense_init(ks[2], d, K * hd, dtype=dtype),
        "wo": dense_init(ks[3], H * hd, d, scale=1.0 / math.sqrt(H * hd),
                         dtype=dtype),
    }


def gqa_apply(p: Params, x: jax.Array, cfg: ArchConfig, *,
              positions: Optional[jax.Array] = None,
              cache: Optional[Params] = None,
              cache_index: Optional[jax.Array] = None,
              kv_src: Optional[jax.Array] = None,
              causal: bool = True,
              use_rope: bool = True,
              impl: str = "xla"):
    """Self- or cross-attention.  Returns (out, new_cache).

    Train/prefill: cache is None, full sequence.
    Decode: cache = {"k": [B,Tmax,K,hd], "v": ...}; x is [B,1,d];
    cache_index is the current write position (scalar int32).
    Cross-attention: kv_src supplies the keys/values source sequence
    (encoder states); no cache update, no causal mask.
    """
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    src = x if kv_src is None else kv_src
    k = (src @ p["wk"]).reshape(B, src.shape[1], K, hd)
    v = (src @ p["wv"]).reshape(B, src.shape[1], K, hd)

    if use_rope and kv_src is None:
        if positions is not None:
            pos = positions
        elif cache is not None:
            pos = cache_index + jnp.arange(S)
        else:
            pos = jnp.arange(S)
        q = apply_rope(q, pos, cfg.rope_theta, cfg.rotary_fraction)
        k = apply_rope(k, pos, cfg.rope_theta, cfg.rotary_fraction)

    new_cache = cache
    k_valid = None
    q_pos = positions
    if cache is not None:
        idx = cache_index
        k = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
        new_cache = {"k": k, "v": v}
        k_valid = jnp.full((B,), idx + S, dtype=jnp.int32)
        q_pos = idx + jnp.arange(S)

    out = sdpa(q, k.astype(q.dtype), v.astype(q.dtype),
               causal=causal and kv_src is None,
               q_positions=q_pos, k_valid_len=k_valid, impl=impl)
    return out.reshape(B, S, H * hd) @ p["wo"], new_cache


def gqa_cache_init(cfg: ArchConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Params:
    K, hd = cfg.n_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, max_len, K, hd), dtype),
            "v": jnp.zeros((batch, max_len, K, hd), dtype)}


# ----------------------------------------------------------------------
# MLA block (deepseek-v3)
# ----------------------------------------------------------------------
def mla_init(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> Params:
    d, H = cfg.d_model, cfg.n_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vd, ql, kl = cfg.v_head_dim, cfg.q_lora_rank, cfg.kv_lora_rank
    ks = jax.random.split(key, 6)
    p = {
        "wkv_a": dense_init(ks[2], d, kl + rope_d, dtype=dtype),
        "kv_norm": rmsnorm_init(kl, dtype),
        "wkv_b": dense_init(ks[3], kl, H * (nope + vd), dtype=dtype),
        "wo": dense_init(ks[4], H * vd, d, scale=1.0 / math.sqrt(H * vd),
                         dtype=dtype),
    }
    if ql:
        p["wq_a"] = dense_init(ks[0], d, ql, dtype=dtype)
        p["q_norm"] = rmsnorm_init(ql, dtype)
        p["wq_b"] = dense_init(ks[1], ql, H * (nope + rope_d), dtype=dtype)
    else:
        p["wq"] = dense_init(ks[0], d, H * (nope + rope_d), dtype=dtype)
    return p


def _mla_q(p: Params, x: jax.Array, cfg: ArchConfig, positions: jax.Array):
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = rmsnorm(p["q_norm"], x @ p["wq_a"], cfg.norm_eps)
        q = (cq @ p["wq_b"]).reshape(B, S, H, nope + rope_d)
    else:
        q = (x @ p["wq"]).reshape(B, S, H, nope + rope_d)
    qn, qr = q[..., :nope], q[..., nope:]
    qr = apply_rope(qr, positions, cfg.rope_theta)
    return qn, qr


def mla_apply(p: Params, x: jax.Array, cfg: ArchConfig, *,
              positions: Optional[jax.Array] = None,
              cache: Optional[Params] = None,
              cache_index: Optional[jax.Array] = None,
              impl: str = "xla"):
    """Returns (out, new_cache).  Cache holds the *compressed* latents:
    {"ckv": [B,Tmax,kv_lora], "kr": [B,Tmax,rope_d]}."""
    B, S, d = x.shape
    H = cfg.n_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vd, kl = cfg.v_head_dim, cfg.kv_lora_rank

    pos = positions if positions is not None else jnp.arange(S)
    if cache is not None:
        pos = cache_index + jnp.arange(S)
    qn, qr = _mla_q(p, x, cfg, pos)

    kv_a = x @ p["wkv_a"]
    ckv = rmsnorm(p["kv_norm"], kv_a[..., :kl], cfg.norm_eps)
    kr = apply_rope(kv_a[..., None, kl:], pos, cfg.rope_theta)[:, :, 0]

    wkv_b = p["wkv_b"].reshape(kl, H, nope + vd)
    wk_b, wv_b = wkv_b[..., :nope], wkv_b[..., nope:]

    if cache is None:
        # naive (train/prefill): expand latents to per-head k,v
        kn = jnp.einsum("btl,lhn->bthn", ckv, wk_b)
        v = jnp.einsum("btl,lhv->bthv", ckv, wv_b)
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kr[:, :, None], (B, S, H, rope_d))],
            axis=-1)
        q = jnp.concatenate([qn, qr], axis=-1)
        out = sdpa(q, k, v, causal=True, q_positions=pos, impl=impl)
        return out.reshape(B, S, H * vd) @ p["wo"], None

    # absorbed decode: attention entirely in latent space
    idx = cache_index
    ckv_c = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, idx, 0))
    kr_c = jax.lax.dynamic_update_slice(
        cache["kr"], kr.astype(cache["kr"].dtype), (0, idx, 0))
    new_cache = {"ckv": ckv_c, "kr": kr_c}

    q_lat = jnp.einsum("bshn,lhn->bshl", qn, wk_b)           # [B,S,H,kl]
    scores = (jnp.einsum("bshl,btl->bhst", q_lat,
                         ckv_c.astype(q_lat.dtype),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", qr,
                           kr_c.astype(qr.dtype),
                           preferred_element_type=jnp.float32))
    scores = scores / math.sqrt(nope + rope_d)
    T = ckv_c.shape[1]
    k_pos = jnp.arange(T)
    q_pos = idx + jnp.arange(S)
    mask = q_pos[:, None] >= k_pos[None, :]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,btl->bshl", probs.astype(ckv_c.dtype), ckv_c)
    out = jnp.einsum("bshl,lhv->bshv", ctx, wv_b.astype(ctx.dtype))
    return out.reshape(B, S, H * vd) @ p["wo"], new_cache


def mla_cache_init(cfg: ArchConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Params:
    return {"ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype)}
