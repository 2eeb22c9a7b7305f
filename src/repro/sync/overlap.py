"""Layer-wise overlapped gradient sync: the Fig. 6 schedule, realized.

``make_synced_scan`` replaces a plain ``lax.scan`` over layer blocks with
a custom-vjp scan whose *backward* emits each layer's parameter-gradient
collective INSIDE the reverse loop body:

- forward: scan saving only each layer's input (== remat by construction),
- backward: reverse scan; per layer, ``jax.vjp`` recomputes the block and
  the layer's dparams are immediately sharding-constrained to a
  data-sharded spec — GSPMD therefore emits a per-layer reduce-scatter
  *inside* the while body, which XLA's async collective scheduler overlaps
  with the next (earlier) layer's backward compute.

This is the paper's co-scheduling insight mapped to TPU semantics
(DESIGN.md §2): the network task (the per-layer RS) becomes an explicit,
ordered, overlappable op instead of one barrier all-reduce after the whole
backward (``sync_mode="barrier"``, the coflow-like baseline).
``tests/test_sync.py`` verifies both the HLO structure (RS inside the loop
vs AR outside) and numerical equality of the gradients.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, RunConfig


def make_grad_sync_fn(mesh, cfg: ArchConfig, run: RunConfig,
                      dp_axes: tuple[str, ...]) -> Callable:
    """Returns sync(dparams_tree) applying a reduce-scatter-inducing
    sharding constraint: the grad keeps its param sharding plus data-
    sharding on the first free, divisible dim."""
    from repro.launch.sharding import param_spec_for, _axsize

    dpsize = 1
    for a in dp_axes:
        dpsize *= mesh.shape[a]

    def one(path, g):
        # Constrain each layer grad to its parameter's sharding.  NOTE: an
        # earlier version additionally injected a dp-sharded dim hoping
        # GSPMD would emit a reduce-scatter (ZeRO-1); measurement showed
        # it lowers as all-reduce + dynamic-slice — same wire bytes — so
        # the hypothesis was refuted and dropped (EXPERIMENTS.md §Perf).
        base = param_spec_for(path, g.shape, cfg, run, mesh)
        entries = list(base) + [None] * (g.ndim - len(base))
        return jax.lax.with_sharding_constraint(
            g, NamedSharding(mesh, P(*entries[:g.ndim])))

    def sync(tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        return jax.tree_util.tree_unflatten(
            treedef, [one(path, g) for path, g in flat])

    return sync


def make_synced_scan(body: Callable, sync: Optional[Callable]):
    """body(bp, x) -> (x_out, aux).  Returns scan(params_stack, x) ->
    (x_final, aux_sum) whose bwd applies ``sync`` to each layer's dparams
    inside the reverse loop."""

    @jax.custom_vjp
    def scan_fn(params_stack, x):
        def step(carry, bp):
            xc, aux = carry
            x2, a = body(bp, xc)
            return (x2, aux + a.astype(jnp.float32)), None

        (xf, aux), _ = jax.lax.scan(
            step, (x, jnp.zeros((), jnp.float32)), params_stack)
        return xf, aux

    def fwd(params_stack, x):
        def step(carry, bp):
            xc, aux = carry
            x2, a = body(bp, xc)
            return (x2, aux + a.astype(jnp.float32)), xc   # save input

        (xf, aux), xs = jax.lax.scan(
            step, (x, jnp.zeros((), jnp.float32)), params_stack)
        return (xf, aux), (params_stack, xs)

    def bwd(res, cts):
        params_stack, xs = res
        dxf, daux = cts

        def step(dx, inp):
            bp, x_in = inp
            _, vjp_fn = jax.vjp(lambda p, xx: body(p, xx), bp, x_in)
            dp, dxin = vjp_fn((dx, daux.astype(jnp.float32)))
            # cast cotangents to the param dtype BEFORE the data-axis
            # reduction: the in-loop grad all-reduce then runs in bf16
            # instead of f32 — halved wire bytes (measured in §Perf)
            dp = jax.tree.map(lambda g, p: g.astype(p.dtype), dp, bp)
            # §Perf iter 6: the inter-layer activation cotangent carries
            # the TP partial-sum ARs; keeping it in the activation dtype
            # (bf16) halves those wire bytes (standard mixed precision)
            dxin = dxin.astype(x_in.dtype)
            if sync is not None:
                with jax.named_scope("grad_sync"):
                    dp = sync(dp)
            return dxin, dp

        dx0, dps = jax.lax.scan(step, dxf, (params_stack, xs),
                                reverse=True)
        return dps, dx0

    scan_fn.defvjp(fwd, bwd)
    return scan_fn
