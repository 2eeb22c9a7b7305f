"""Plain reference of a Mamba-2 language model (arXiv:2405.21060).

Embedding, then per layer ``x + Mamba2(RMSNorm(x))``, a final RMSNorm
and the LM head tied to the embedding.  The mixer: one input projection
to (z, xBC, dt); a depthwise causal convolution over xBC with SiLU;
dt = softplus(dt + dt_bias); A = -exp(A_log); the SSD scan on
(x·dt, A·dt, B, C) in the chunked form of the paper's Listing 1; the
skip D·x; the gated RMSNorm ``RMSNorm(y · SiLU(z))``; the output
projection.  Everything runs in float32 (see ``common``).

The weight recipe draws every seed's weights in the layout the
configuration's program uses (``init``), so both sides start from the
same numbers without the reference taking any from the program.
"""
from __future__ import annotations

import math
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import (F32, embedding, follow, nll_sum,  # noqa: E402
                    normal_weight, rmsnorm, stack_layers)


def dims(c: dict) -> dict:
    s = c["ssm_cfg"]
    d = c["d_model"]
    d_in = s["expand"] * d
    G, N = s["ngroups"], s["d_state"]
    return {"d": d, "d_in": d_in, "H": d_in // s["headdim"],
            "P": s["headdim"], "G": G, "N": N, "W": s["d_conv"],
            "Q": s["chunk_size"], "conv": d_in + 2 * G * N,
            "L": c["n_layer"], "V": c["vocab_size"],
            "eps": c["norm_epsilon"]}


def expect(c: dict) -> dict:
    """The program's ``ArchConfig`` fields this file fixes."""
    k = dims(c)
    return {"n_layers": k["L"], "d_model": k["d"], "vocab_size": k["V"],
            "d_ff": c["d_intermediate"], "ssm_state": k["N"],
            "ssm_expand": c["ssm_cfg"]["expand"], "ssm_head_dim": k["P"],
            "ssm_conv": k["W"], "ssm_n_groups": k["G"],
            "ssm_chunk": k["Q"], "tie_embeddings": c["tie_embeddings"],
            "norm_eps": k["eps"], "layer_pattern": ("mamba",)}


def flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs of one trained token: 2 per multiply-add, times 3 for
    the forward and backward passes.  Projections, LM head, and the SSD
    products at chunk length Q: the causal intra-chunk products (C·B
    over the groups, then with x over the heads) and the chunk states
    into and out of each chunk.  The convolution, the norms and the
    inter-chunk recurrence are left out; recomputation is not counted."""
    k = dims(c)
    proj = 2 * k["d"] * (2 * k["d_in"] + 2 * k["G"] * k["N"] + k["H"]) \
        + 2 * k["d_in"] * k["d"]
    ssd = (min(k["Q"], seq) + 1) * (k["G"] * k["N"] + k["H"] * k["P"]) \
        + 4 * k["H"] * k["P"] * k["N"]
    head = 2 * k["d"] * k["V"]
    return 3.0 * (k["L"] * (proj + ssd) + head)


def init(c: dict, seed: int) -> dict:
    k = dims(c)
    dt = jnp.dtype(c["dtypes"]["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    layer_keys = jax.random.split(jax.random.fold_in(keys[2], 0), k["L"])
    layers = []
    for lk in layer_keys:
        ks = jax.random.split(jax.random.split(lk, 1)[0], 6)
        s = jax.random.split(ks[0], 4)
        layers.append([{
            "ln1": jnp.ones((k["d"],), dt),
            "ssm": {
                "in_proj": normal_weight(
                    s[0], k["d"], 2 * k["d_in"] + 2 * k["G"] * k["N"]
                    + k["H"], dt),
                "conv_w": (jax.random.normal(s[1], (k["W"], k["conv"]), F32)
                           / math.sqrt(k["W"])).astype(dt),
                "conv_b": jnp.zeros((k["conv"],), dt),
                "A_log": jnp.log(jnp.linspace(1.0, 16.0, k["H"])
                                 .astype(F32)),
                "D": jnp.ones((k["H"],), F32),
                "dt_bias": jnp.zeros((k["H"],), F32),
                "norm_w": jnp.ones((k["d_in"],), dt),
                "out_proj": normal_weight(s[3], k["d_in"], k["d"], dt),
            }}])
    return {"embed": embedding(keys[0], k["V"], k["d"], dt),
            "final_norm": jnp.ones((k["d"],), dt),
            "segments": [stack_layers(layers)]}


def _segsum(x):
    """out[..., i, j] = x[..., j+1] + ... + x[..., i] for j <= i."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), out, -jnp.inf)


def ssd(X, A, B, C, Q, pol):
    """Listing 1 of arXiv:2405.21060.  X [b,L,h,p] (already x·dt),
    A [b,L,h] (A·dt), B, C [b,L,h,n]; returns Y [b,L,h,p]."""
    b, L, h, p = X.shape
    c = L // Q
    X = X.reshape(b, c, Q, h, p)
    B = B.reshape(b, c, Q, h, -1)
    C = C.reshape(b, c, Q, h, -1)
    A = A.reshape(b, c, Q, h).transpose(0, 3, 1, 2)          # b h c l
    A_cum = jnp.cumsum(A, axis=-1)
    Lm = jnp.exp(_segsum(A))                                 # b h c l s
    Y_diag = pol.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, Lm, X)
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)
    states = pol.einsum("bclhn,bhcl,bclhp->bchpn", B, decay_states, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(_segsum(
        jnp.pad(A_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = pol.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    Y_off = pol.einsum("bclhn,bchpn,bhcl->bclhp", C, states,
                       jnp.exp(A_cum))
    return (Y_diag + Y_off).reshape(b, L, h, p)


def mixer(p: dict, h: jax.Array, k: dict, pol) -> jax.Array:
    b, L, _ = h.shape
    zxbcdt = pol.einsum("bld,de->ble", h, p["in_proj"])
    z = zxbcdt[..., :k["d_in"]]
    xBC = zxbcdt[..., k["d_in"]:k["d_in"] + k["conv"]]
    dt = zxbcdt[..., -k["H"]:]
    W = k["W"]
    xp = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    conv = sum(xp[:, i:i + L] * w[i] for i in range(W)) \
        + p["conv_b"].astype(F32)
    xBC = jax.nn.silu(conv)
    GN = k["G"] * k["N"]
    x = xBC[..., :k["d_in"]].reshape(b, L, k["H"], k["P"])
    hpg = k["H"] // k["G"]
    Bm = jnp.repeat(xBC[..., k["d_in"]:k["d_in"] + GN]
                    .reshape(b, L, k["G"], k["N"]), hpg, axis=2)
    Cm = jnp.repeat(xBC[..., k["d_in"] + GN:]
                    .reshape(b, L, k["G"], k["N"]), hpg, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = ssd(x * dt[..., None], A * dt, Bm, Cm, min(k["Q"], L), pol)
    y = y + p["D"][:, None] * x
    y = rmsnorm(y.reshape(b, L, k["d_in"]) * jax.nn.silu(z), p["norm_w"],
                k["eps"])
    return pol.einsum("ble,ed->bld", y, p["out_proj"])


def loss_sum(c: dict, params: dict, tokens: jax.Array, pol) -> jax.Array:
    k = dims(c)
    x = pol.act(params["embed"][tokens].astype(F32))

    @jax.checkpoint
    def layer(x, lp):
        lp = lp[0]
        return pol.act(x + mixer(lp["ssm"], rmsnorm(x, lp["ln1"], k["eps"]),
                                 k, pol)), None

    x, _ = jax.lax.scan(layer, x, params["segments"][0])
    x = rmsnorm(x[:, :-1], params["final_norm"], k["eps"])
    logits = pol.einsum("bsd,vd->bsv", x, params["embed"])
    return nll_sum(logits, tokens[:, 1:])


def readings(config: dict, batches, seed: int, precision: str = "f32",
             rows=None) -> dict:
    return follow(init, loss_sum, config, batches, seed, precision, rows)
