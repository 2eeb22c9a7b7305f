"""Plain reference of a dense Llama-architecture language model
(DeepSeek LLM, arXiv:2401.02954).

Embedding, then per layer ``x + Attn(RMSNorm(x))`` and
``x + SwiGLU(RMSNorm(x))``, a final RMSNorm and an untied LM head.
Attention is causal softmax attention over heads of ``head_dim``, with
rotary embeddings of base ``rope_theta`` applied to q and k.  Each
rotated pair is two adjacent dims (2i, 2i+1), the program's layout; the
published checkpoint pairs dim i with dim i + head_dim/2, which is the
same function after a fixed permutation of the q and k projections'
columns, so random weights lose nothing by it.  Attention is taken in
blocks of queries so that it fits; every block sees every key.
Everything runs in float32 (see ``common``).
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import (F32, embedding, follow, nll_sum,  # noqa: E402
                    normal_weight, rmsnorm, stack_layers)

QUERY_BLOCK = 512


def dims(c: dict) -> dict:
    H = c["num_attention_heads"]
    d = c["hidden_size"]
    return {"d": d, "H": H, "K": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // H,
            "ff": c["intermediate_size"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"], "eps": c["rms_norm_eps"],
            "theta": c["rope_theta"]}


def expect(c: dict) -> dict:
    """The program's ``ArchConfig`` fields this file fixes."""
    k = dims(c)
    return {"n_layers": k["L"], "d_model": k["d"], "n_heads": k["H"],
            "n_kv_heads": k["K"], "head_dim": k["hd"], "d_ff": k["ff"],
            "vocab_size": k["V"], "norm_eps": k["eps"],
            "rope_theta": k["theta"], "rotary_fraction": 1.0,
            "mlp_type": "swiglu", "attn_type": "gqa",
            "tie_embeddings": c["tie_word_embeddings"], "n_experts": 0}


def flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs of one trained token: 2 per multiply-add, times 3 for
    the forward and backward passes, across the q/k/v/o projections, the
    SwiGLU MLP, the LM head, and causal attention's QK and PV (a token
    at position t attends to t + 1 keys: (seq + 1) / 2 on average).
    Norms and RoPE are left out; recomputation is not counted."""
    k = dims(c)
    proj = 2 * k["d"] * (k["H"] + 2 * k["K"]) * k["hd"] \
        + 2 * k["H"] * k["hd"] * k["d"]
    mlp = 3 * 2 * k["d"] * k["ff"]
    attn = 2 * 2 * k["H"] * k["hd"] * (seq + 1) / 2
    return 3.0 * (k["L"] * (proj + mlp + attn) + 2 * k["d"] * k["V"])


def init(c: dict, seed: int) -> dict:
    k = dims(c)
    dt = jnp.dtype(c["dtypes"]["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    layer_keys = jax.random.split(jax.random.fold_in(keys[2], 0), k["L"])
    layers = []
    for lk in layer_keys:
        ks = jax.random.split(jax.random.split(lk, 1)[0], 6)
        a = jax.random.split(ks[0], 4)
        f = jax.random.split(ks[2], 3)
        layers.append([{
            "ln1": jnp.ones((k["d"],), dt),
            "attn": {"wq": normal_weight(a[0], k["d"], k["H"] * k["hd"], dt),
                     "wk": normal_weight(a[1], k["d"], k["K"] * k["hd"], dt),
                     "wv": normal_weight(a[2], k["d"], k["K"] * k["hd"], dt),
                     "wo": normal_weight(a[3], k["H"] * k["hd"], k["d"], dt)},
            "ln2": jnp.ones((k["d"],), dt),
            "mlp": {"w_out": normal_weight(f[2], k["ff"], k["d"], dt),
                    "w_in": normal_weight(f[0], k["d"], k["ff"], dt),
                    "w_gate": normal_weight(f[1], k["d"], k["ff"], dt)}}])
    return {"embed": embedding(keys[0], k["V"], k["d"], dt),
            "final_norm": jnp.ones((k["d"],), dt),
            "lm_head": normal_weight(keys[1], k["d"], k["V"], dt),
            "segments": [stack_layers(layers)]}


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x [b,S,h,hd]: rotate each adjacent pair (2i, 2i+1) at position s
    by s · theta^(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv              # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, pol):
    """Causal softmax attention, q [b,S,H,hd], k/v [b,S,K,hd]."""
    b, S, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = min(QUERY_BLOCK, S)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = pol.einsum("bqhd,bkhd->bhqk", qi, k) / jnp.sqrt(F32(hd))
        mask = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return pol.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(block, jnp.arange(S // qb))        # [n,b,qb,H,hd]
    return jnp.moveaxis(out, 0, 1).reshape(b, S, H, hd)


def loss_sum(c: dict, params: dict, tokens: jax.Array, pol) -> jax.Array:
    k = dims(c)
    x = pol.act(params["embed"][tokens])
    b, S = tokens.shape

    @jax.checkpoint
    def layer(x, lp):
        lp = lp[0]
        a, f = lp["attn"], lp["mlp"]
        h = rmsnorm(x, lp["ln1"], k["eps"])
        q = pol.einsum("bsd,de->bse", h, a["wq"]).reshape(b, S, k["H"], -1)
        kk = pol.einsum("bsd,de->bse", h, a["wk"]).reshape(b, S, k["K"], -1)
        v = pol.einsum("bsd,de->bse", h, a["wv"]).reshape(b, S, k["K"], -1)
        o = attention(rope(q, k["theta"]), rope(kk, k["theta"]), v, pol)
        x = pol.act(x + pol.einsum("bse,ed->bsd", o.reshape(b, S, -1),
                                   a["wo"]))
        h = rmsnorm(x, lp["ln2"], k["eps"])
        g = jax.nn.silu(pol.einsum("bsd,df->bsf", h, f["w_gate"])) \
            * pol.einsum("bsd,df->bsf", h, f["w_in"])
        return pol.act(x + pol.einsum("bsf,fd->bsd", g, f["w_out"])), None

    x, _ = jax.lax.scan(layer, x, params["segments"][0])
    x = rmsnorm(x[:, :-1], params["final_norm"], k["eps"])
    logits = pol.einsum("bsd,dv->bsv", x, params["lm_head"])
    return nll_sum(logits, tokens[:, 1:])


def readings(config: dict, batches, seed: int, precision: str = "f32",
             rows=None) -> dict:
    return follow(init, loss_sum, config, batches, seed, precision, rows)
