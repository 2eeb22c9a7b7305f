"""Mixture-of-Experts with expert parallelism over the "model" mesh axis.

Design (DESIGN.md §6): activations are sharded over the data axes and
*replicated* over "model"; expert weights are sharded over "model" (EP).
Inside a ``shard_map`` region each model shard:

1. computes the (replicated) router for its data shard's tokens,
2. sorts token→expert assignments and gathers capacity-bounded blocks for
   its *local* experts only,
3. runs the expert FFNs as one batched einsum (MXU-friendly),
4. scatter-adds gated outputs and combines across expert shards with a
   single ``psum`` (or ``psum_scatter`` — a hillclimb lever) that also
   folds in the TP-sharded shared-expert partials.

The psum here is an explicit network MXTask in the training step's MXDAG;
benchmark fig6 and the sync planner reason about it.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import dense_init

Params = dict


def moe_init(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> Params:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    ks = jax.random.split(key, 7)
    scale = 1.0 / math.sqrt(d)

    def experts(k):
        return (jax.random.normal(k, (E, d, f), jnp.float32) * scale
                ).astype(dtype)

    p = {
        "router": dense_init(ks[0], d, E, dtype=jnp.float32),
        "w_in": experts(ks[1]),
        "w_gate": experts(ks[2]),
        "w_out": (jax.random.normal(ks[3], (E, f, d), jnp.float32)
                  / math.sqrt(f)).astype(dtype),
    }
    if cfg.n_shared_experts:
        sf = cfg.n_shared_experts * f
        p["shared_in"] = dense_init(ks[4], d, sf, dtype=dtype)
        p["shared_gate"] = dense_init(ks[5], d, sf, dtype=dtype)
        p["shared_out"] = dense_init(ks[6], sf, d, dtype=dtype)
    return p


def _capacity(n_tokens: int, cfg: ArchConfig) -> int:
    c = int(math.ceil(cfg.capacity_factor * n_tokens
                      * cfg.n_experts_per_tok / cfg.n_experts))
    return max(8, -(-c // 8) * 8)          # >=8, multiple of 8


def _local_moe(x2: jax.Array, router: jax.Array, w_in, w_gate, w_out,
               shared, cfg: ArchConfig, ep: int, combine: str,
               in_shard_map: bool = True):
    """Body run per model shard.  x2: [T, d] (this data shard's tokens,
    replicated over model); w_*: local expert slices [E/ep, d|f, f|d]."""
    T, d = x2.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    E_loc = E // ep
    C = _capacity(T, cfg)
    rank = jax.lax.axis_index("model") if in_shard_map else 0

    logits = (x2.astype(jnp.float32) @ router)             # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)                   # [T, k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # load-balance aux (Switch-style), identical on every model shard
    assign = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], ids].add(1.0 / k)
    f_e = jnp.mean(jax.lax.stop_gradient(assign), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(f_e * p_e)

    # sort assignments by expert id
    flat_ids = ids.reshape(-1)                             # [T*k]
    order = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[order]
    sorted_tok = order // k
    sorted_gate = gates.reshape(-1)[order]

    first = rank * E_loc
    bounds = first + jnp.arange(E_loc + 1)
    edges = jnp.searchsorted(sorted_ids, bounds)
    starts, ends = edges[:-1], edges[1:]
    counts = ends - starts

    slot = starts[:, None] + jnp.arange(C)[None, :]        # [E_loc, C]
    valid = jnp.arange(C)[None, :] < jnp.minimum(counts, C)[:, None]
    slot = jnp.where(valid, slot, 0)
    tok = sorted_tok[slot]                                 # [E_loc, C]
    gate = jnp.where(valid, sorted_gate[slot], 0.0)        # [E_loc, C]

    xe = x2[tok]                                           # [E_loc, C, d]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", xe, w_in)
    ye = jnp.einsum("ecf,efd->ecd", h, w_out)
    ye = ye * gate[..., None].astype(ye.dtype)

    y = jnp.zeros((T, d), ye.dtype).at[tok.reshape(-1)].add(
        ye.reshape(-1, d))

    if shared is not None:
        sh_in, sh_gate, sh_out = shared                    # TP over model
        hs = jax.nn.silu(x2 @ sh_gate) * (x2 @ sh_in)
        y = y + hs @ sh_out                                # partial: psum'd

    if in_shard_map:
        # always combine across the model axis (marks the result invariant
        # over "model" even when ep == 1, where the psum is a no-op)
        if combine == "psum_scatter":
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=0,
                                     tiled=True)
            y = jax.lax.all_gather(y, "model", axis=0, tiled=True)
        else:
            y = jax.lax.psum(y, "model")
    return y, aux[None]


def moe_apply(p: Params, x: jax.Array, cfg: ArchConfig, *,
              mesh: Optional[jax.sharding.Mesh],
              dp_axes: tuple[str, ...] = ("data",),
              combine: str = "psum"):
    """x: [B, S, d] sharded over dp_axes on B.  Returns (y, aux_loss)."""
    B, S, d = x.shape
    shared = None
    has_shared = "shared_in" in p
    if mesh is None or "model" not in mesh.axis_names:
        ep = 1
        shared = ((p["shared_in"], p["shared_gate"], p["shared_out"])
                  if has_shared else None)
        y2, aux = _local_moe(x.reshape(-1, d), p["router"], p["w_in"],
                             p["w_gate"], p["w_out"], shared, cfg, 1,
                             combine, in_shard_map=False)
        return y2.reshape(B, S, d), jnp.mean(aux)

    ep = mesh.shape["model"]
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if B % max(dp_size, 1) != 0:
        dp = ()          # e.g. batch-1 decode: tokens replicated over dp
    xspec = P(dp if dp else None, None, None)
    espec = P("model", None, None)

    def body(x_, router, w_in, w_gate, w_out, *shared_w):
        sh = tuple(shared_w) if shared_w else None
        y2, aux = _local_moe(x_.reshape(-1, d), router, w_in, w_gate,
                             w_out, sh, cfg, ep, combine)
        return y2.reshape(x_.shape), aux

    in_specs = [xspec, P(), espec, espec, espec]
    args = [x, p["router"], p["w_in"], p["w_gate"], p["w_out"]]
    if has_shared:
        in_specs += [P(None, "model"), P(None, "model"), P("model", None)]
        args += [p["shared_in"], p["shared_gate"], p["shared_out"]]

    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(xspec, P(dp) if dp else P(None)))(*args)
    return y, jnp.mean(aux)
