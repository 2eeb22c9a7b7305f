"""Pieces of the plain references that every configuration shares: the
weight recipe's initialisers, RMSNorm, cross-entropy, AdamW and the
loop that follows the program's first training steps.

Nothing here imports the program.  Activations, gradients and the
optimizer run in float32 with matrix products at ``HIGHEST`` precision
(on a TPU a float32 product otherwise runs in bf16 passes); parameters
are stored as the configuration states (``dtypes.params``), so the
reference rounds them there after each update as the program must.

``Policy("fp8")`` is the control: every matrix product takes its
operands through float8 e4m3 with a per-tensor scale, the step below the
bf16 that the configurations state.  ``Policy("fp8act")`` also keeps the
residual stream in float8 e4m3: each layer's output, and its gradient on
the way back, rounded there.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _e4m3(x: jax.Array) -> jax.Array:
    """``x`` through float8 e4m3 with a per-tensor scale."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@jax.custom_vjp
def _e4m3_both_ways(x):
    return _e4m3(x)


_e4m3_both_ways.defvjp(lambda x: (_e4m3(x), None),
                       lambda _, g: (_e4m3(g),))


class Policy:
    """How the operands of a matrix product are taken: ``f32`` as they
    are, ``fp8`` and ``fp8act`` through float8 e4m3 with a per-tensor
    scale.  ``act`` is the residual stream: ``fp8act`` rounds it, and its
    gradient, to float8 e4m3."""

    def __init__(self, name: str):
        if name not in ("f32", "fp8", "fp8act"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name

    def operand(self, x: jax.Array) -> jax.Array:
        x = x.astype(F32)
        if self.name == "f32":
            return x
        q = _e4m3(jax.lax.stop_gradient(x))
        # the rounded value forward, the identity backward
        return x + jax.lax.stop_gradient(q - x)

    def act(self, x: jax.Array) -> jax.Array:
        return _e4m3_both_ways(x) if self.name == "fp8act" else x

    def einsum(self, spec: str, *ops: jax.Array) -> jax.Array:
        return jnp.einsum(spec, *[self.operand(o) for o in ops],
                          precision=HIGHEST, preferred_element_type=F32)


# ---------------------------------------------------------------------------
# the weight recipe (what every seed draws; see each reference's init)
# ---------------------------------------------------------------------------
def normal_weight(key, d_in: int, d_out: int, dtype,
                  scale: Optional[float] = None) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in) if scale is None else scale
    return (jax.random.normal(key, (d_in, d_out), F32) * scale).astype(dtype)


def embedding(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), F32) * 0.02).astype(dtype)


def stack_layers(layers: list) -> dict:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def nll_sum(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Sum over positions of -log softmax(logits)[label]."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - lab)


def leaf_names(tree) -> list[str]:
    def name(path):
        out = []
        for p in path:
            if isinstance(p, jax.tree_util.DictKey):
                out.append(str(p.key))
            elif isinstance(p, jax.tree_util.SequenceKey):
                out.append(str(p.idx))
            else:
                out.append(str(p))
        return "/".join(out)
    return [name(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


# ---------------------------------------------------------------------------
# AdamW as the configuration states it
# ---------------------------------------------------------------------------
def learning_rate(train: dict, k: int) -> float:
    """Cosine schedule with linear warm-up; ``k`` counts steps from 1."""
    peak, w, total = train["lr"], train["warmup"], train["schedule_steps"]
    if k < w:
        return peak * k / max(w, 1)
    t = min(max((k - w) / max(total - w, 1), 0.0), 1.0)
    fl = train["lr_floor"]
    return peak * (fl + (1 - fl) * 0.5 * (1 + math.cos(math.pi * t)))


def _adamw(params, m, v, grads, k, lr, *, train):
    b1, b2, eps = train["b1"], train["b2"], train["eps"]
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, train["clip_norm"] / (gnorm + 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    bc1, bc2 = 1 - b1 ** k, 1 - b2 ** k

    def one(p, g, mi, vi):
        mi = b1 * mi + (1 - b1) * g
        vi = b2 * vi + (1 - b2) * g * g
        delta = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps)
        # decay keys on the rank of the leaf as stored: stacked per-layer
        # vectors are rank 2 and decay, unstacked ones do not
        wd = train["weight_decay"] if p.ndim >= 2 else 0.0
        pf = p.astype(F32)
        return (pf - lr * (delta + wd * pf)).astype(p.dtype), mi, vi

    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


# ---------------------------------------------------------------------------
def follow(init: Callable, loss_sum: Callable, config: dict,
           batches: list[np.ndarray], seed: int, precision: str = "f32",
           rows: Optional[int] = None) -> dict:
    """The program's first ``len(batches)`` training steps, done plainly.

    ``init(config, seed)`` draws the weights; ``loss_sum(config,
    params_f32, tokens, policy)`` is the sum over a block of rows of the next-token
    negative log-likelihood.  The batch is taken in blocks of
    ``config["reference_rows"]`` rows and the gradients summed, so that
    the reference fits beside nothing else on one chip.  ``rows`` keeps
    only the first rows of each batch (a planted fault: the mean over
    part of the batch).

    Returns each step's loss, each leaf's gradient norm at step 1 (as
    the optimizer gets it, after clipping) and each leaf's change over
    all the steps, by leaf name.
    """
    pol = Policy(precision)
    train = config["train"]
    params = jax.jit(lambda: init(config, seed))()
    p0 = jax.device_get(params)
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)

    def acc_grad(p, toks, n, acc):
        pf = jax.tree.map(lambda x: x.astype(F32), p)
        val, g = jax.value_and_grad(
            lambda q: loss_sum(config, q, toks, pol) / n)(pf)
        return val, jax.tree.map(jnp.add, acc, g)

    grad = jax.jit(acc_grad, donate_argnums=(3,))
    step = jax.jit(functools.partial(_adamw, train=train),
                   donate_argnums=(1, 2, 3))
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                               for x in jax.tree.leaves(t)])
    block = config["reference_rows"]
    losses, g1 = [], None
    for k, batch in enumerate(batches, start=1):
        batch = batch if rows is None else batch[:rows]
        n = float(batch.shape[0] * (batch.shape[1] - 1))
        loss = 0.0
        grads = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        for r in range(0, batch.shape[0], block):
            val, grads = grad(params, jnp.asarray(batch[r:r + block]), n,
                              grads)
            loss += float(val)
        params, m, v = step(params, m, v, grads, float(k),
                            learning_rate(train, k))
        del grads
        if k == 1:
            g1 = [float(x) / (1 - train["b1"]) for x in norms(m)]
        losses.append(loss)
    del m, v
    pn = jax.device_get(params)
    names = leaf_names(params)
    change = [float(np.linalg.norm(b.astype(np.float32).ravel()
                                   - a.astype(np.float32).ravel()))
              for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(pn))]
    return {"loss": losses, "grad_norm": dict(zip(names, g1)),
            "change_norm": dict(zip(names, change))}
