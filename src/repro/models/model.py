"""Model assembly: every assigned architecture as one composable stack.

A config is compiled into *segments*: maximal runs of a repeating layer
pattern (e.g. jamba's period-8 [m m m m a m m m] × 4, deepseek-v3's
3 dense + 58 MoE).  Each segment's parameters are stacked on a leading
repeat axis and executed with ``jax.lax.scan`` — the HLO contains each
distinct block *once*, which keeps 512-device compiles tractable
(DESIGN.md §6).

The Model exposes:
- ``init(rng)``                     → params pytree
- ``loss(params, batch)``           → (scalar loss, metrics) for train_step
- ``forward(params, batch)``        → logits (prefill)
- ``init_cache(batch, max_len)``    → decode cache pytree
- ``decode_step(params, cache, tokens, index)`` → (logits, cache)

With ``RunConfig.remat`` the scan body is rematerialised in the backward
pass.  ``Model.saved_tiers`` (set by ``launch/train.py:build_trainer``
from ``remat_saves``) names the residuals it keeps instead of
recomputing them.
"""
from __future__ import annotations

import collections
import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RunConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    cross_entropy, dense_init, embed_init, mlp, mlp_init, rmsnorm,
    rmsnorm_init,
)

Params = dict


# ----------------------------------------------------------------------
# segment derivation
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BlockSpec:
    mixer: str           # "attn" | "mamba"
    ffn: str             # "dense" | "moe" | "none"
    causal: bool = True
    cross: bool = False  # decoder cross-attention (whisper)


@dataclasses.dataclass(frozen=True)
class Segment:
    pattern: tuple[BlockSpec, ...]
    repeats: int


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def derive_segments(cfg: ArchConfig, *, cross: bool = False,
                    causal: bool = True) -> list[Segment]:
    def spec(i: int) -> BlockSpec:
        mixer = cfg.pattern[i % len(cfg.pattern)]
        if cfg.is_moe_layer(i):
            ffn = "moe"
        elif cfg.d_ff > 0:
            ffn = "dense"
        else:
            ffn = "none"
        if mixer == "mamba":
            ffn = ffn if cfg.family == "hybrid" else \
                ("none" if cfg.d_ff == 0 else ffn)
        return BlockSpec(mixer=mixer, ffn=ffn, causal=causal, cross=cross)

    regions = []
    if cfg.first_dense_layers:
        regions.append((0, cfg.first_dense_layers))
        regions.append((cfg.first_dense_layers, cfg.n_layers))
    else:
        regions.append((0, cfg.n_layers))

    segments = []
    for (lo, hi) in regions:
        n = hi - lo
        if n <= 0:
            continue
        period = _lcm(len(cfg.pattern),
                      cfg.moe_layer_period if cfg.n_experts else 1)
        if n % period != 0:
            period = n
        pat = tuple(spec(lo + j) for j in range(period))
        segments.append(Segment(pattern=pat, repeats=n // period))
    return segments


# ----------------------------------------------------------------------
# what the layer scan's remat keeps
# ----------------------------------------------------------------------
# Tiers of residuals, by their ``checkpoint_name``s (``layers.mlp``,
# ``kernels/ops.py:_flash_fwd``), in the order ``remat_saves`` takes
# them: most recompute removed per byte kept first (PERF.md §5).
REMAT_TIERS = {"core": ("core_out", "core_lse"),
               "mlp": ("mlp_gate", "mlp_up"),
               "qkv": ("qkv",)}

# Bytes one device keeps across the remat, by the tiers kept ("core,mlp"),
# tallied per scanned segment at trace time.
REMAT: collections.Counter = collections.Counter()


def remat_saves(tier_bytes: dict[str, int],
                budget: Optional[float]) -> tuple[str, ...]:
    """The tiers the layer scan keeps: taken in ``REMAT_TIERS``' order
    while the bytes they keep over all scanned layers (``tier_bytes``,
    one device's) fit in ``budget``, up to the first that does not.  A
    tier of no bytes (the model never names it) is passed over; without
    a budget (a device that reports no memory) nothing is kept."""
    if budget is None:
        return ()
    kept, total = [], 0
    for tier in REMAT_TIERS:
        b = tier_bytes.get(tier, 0)
        if not b:
            continue
        if total + b > budget:
            break
        kept.append(tier)
        total += b
    return tuple(kept)


# ----------------------------------------------------------------------
# per-block init / apply
# ----------------------------------------------------------------------
def _block_init(key, spec: BlockSpec, cfg: ArchConfig, dtype) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"ln1": rmsnorm_init(cfg.d_model, dtype)}
    if spec.mixer == "attn":
        if cfg.attn_type == "mla":
            p["attn"] = attn.mla_init(ks[0], cfg, dtype=dtype)
        else:
            p["attn"] = attn.gqa_init(ks[0], cfg, dtype=dtype)
    else:
        p["ssm"] = ssm_mod.ssm_init(ks[0], cfg, dtype=dtype)
    if spec.cross:
        p["ln_x"] = rmsnorm_init(cfg.d_model, dtype)
        p["xattn"] = attn.gqa_init(ks[1], cfg, cross=True, dtype=dtype)
    if spec.ffn == "dense":
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        p["mlp"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_type,
                            dtype=dtype)
    elif spec.ffn == "moe":
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        p["moe"] = moe_mod.moe_init(ks[3], cfg, dtype=dtype)
    return p


class Model:
    def __init__(self, cfg: ArchConfig, run: RunConfig = RunConfig(), *,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 dp_axes: tuple[str, ...] = ("data",),
                 dtype=jnp.bfloat16):
        self.cfg = cfg
        self.run = run
        self.mesh = mesh
        self.dp_axes = dp_axes
        self.dtype = dtype
        # GSPMD cannot partition a Mosaic kernel: over several devices
        # "auto" keeps attention on the XLA paths.
        self.attn_impl = run.attn_impl
        if run.attn_impl == "auto" and mesh is not None and mesh.size > 1:
            self.attn_impl = "xla_flash"
        # the REMAT_TIERS the layer scan's remat keeps (remat_saves)
        self.saved_tiers: tuple[str, ...] = ()
        self.segments = derive_segments(cfg)
        self.enc_segments: list[Segment] = []
        if cfg.encoder_layers:
            self.enc_segments = [Segment(
                pattern=(BlockSpec("attn", "dense", causal=False),),
                repeats=cfg.encoder_layers)]
            # decoder blocks get cross-attention
            self.segments = [Segment(
                pattern=tuple(dataclasses.replace(s, cross=True)
                              for s in seg.pattern),
                repeats=seg.repeats) for seg in self.segments]

    # ------------------------------------------------------------------
    def init(self, rng) -> Params:
        cfg, dtype = self.cfg, self.dtype
        keys = jax.random.split(rng, 8)
        p: Params = {
            "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
            "final_norm": rmsnorm_init(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(keys[1], cfg.d_model,
                                      self._vocab_padded(), dtype=dtype)
        p["segments"] = []
        for i, seg in enumerate(self.segments):
            skeys = jax.random.split(jax.random.fold_in(keys[2], i),
                                     seg.repeats)

            def init_one(k, seg=seg):
                pks = jax.random.split(k, len(seg.pattern))
                return [_block_init(pk, sp, cfg, dtype)
                        for pk, sp in zip(pks, seg.pattern)]

            p["segments"].append(jax.vmap(init_one)(skeys))
        if cfg.encoder_layers:
            ekeys = jax.random.split(keys[3], cfg.encoder_layers)
            espec = self.enc_segments[0].pattern[0]
            p["encoder"] = jax.vmap(
                lambda k: _block_init(k, espec, cfg, dtype))(ekeys)
            p["enc_norm"] = rmsnorm_init(cfg.d_model, dtype)
        if cfg.vision_embed_dim:
            p["vis_proj"] = dense_init(keys[4], cfg.vision_embed_dim,
                                       cfg.d_model, dtype=dtype)
        if cfg.mtp:
            p["mtp"] = {
                "proj": dense_init(keys[5], 2 * cfg.d_model, cfg.d_model,
                                   dtype=dtype),
                "block": _block_init(keys[6],
                                     BlockSpec("attn", "dense"), cfg, dtype),
                "ln": rmsnorm_init(cfg.d_model, dtype),
            }
        return p

    def tier_bytes(self, seg: Segment, batch: int, seq: int
                   ) -> dict[str, int]:
        """Bytes each of ``REMAT_TIERS`` keeps over ``seg``'s scan for a
        ``[batch, seq]`` input on one device.  The core and qkv residuals
        exist only where attention takes the flash kernels."""
        cfg = self.cfg
        item = jnp.dtype(self.dtype).itemsize
        H = cfg.n_heads
        if cfg.attn_type == "mla":
            K, hd = H, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            hdv = cfg.v_head_dim
        else:
            K, hd, hdv = cfg.n_kv_heads, cfg.head_dim, cfg.head_dim
        q = jax.ShapeDtypeStruct((batch, seq, H, hd), self.dtype)
        k = jax.ShapeDtypeStruct((batch, seq, K, hd), self.dtype)
        v = jax.ShapeDtypeStruct((batch, seq, K, hdv), self.dtype)
        tokens = batch * seq
        out = dict.fromkeys(REMAT_TIERS, 0)
        for spec in seg.pattern:
            if spec.mixer == "attn" and attn._path(
                    self.attn_impl, q, k, v, causal=spec.causal,
                    q_positions=None, k_valid_len=None) == "kernel":
                out["core"] += tokens * H * (hdv * item + 4)  # o, f32 lse
                out["qkv"] += tokens * (H * hd + K * (hd + hdv)) * item
            if spec.ffn == "dense":
                n = 2 if cfg.mlp_type == "swiglu" else 1
                out["mlp"] += tokens * cfg.d_ff * n * item
        return {t: b * seg.repeats for t, b in out.items()}

    def dp_size(self) -> int:
        """Devices the batch is split over."""
        if self.mesh is None:
            return 1
        return math.prod(self.mesh.shape[a] for a in self.dp_axes)

    # ------------------------------------------------------------------
    def _apply_block(self, bp: Params, spec: BlockSpec, x, *,
                     positions=None, cache=None, cache_index=None,
                     enc_out=None):
        cfg, run = self.cfg, self.run
        aux = jnp.zeros((), jnp.float32)
        new_cache = {}
        with jax.named_scope("attention" if spec.mixer == "attn"
                             else "ssm"):
            h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
            if spec.mixer == "attn":
                c = cache.get("attn") if cache else None
                if cfg.attn_type == "mla":
                    out, nc = attn.mla_apply(bp["attn"], h, cfg,
                                             positions=positions, cache=c,
                                             cache_index=cache_index,
                                             impl=self.attn_impl)
                else:
                    out, nc = attn.gqa_apply(bp["attn"], h, cfg,
                                             positions=positions, cache=c,
                                             cache_index=cache_index,
                                             causal=spec.causal,
                                             impl=self.attn_impl)
                if nc is not None:
                    new_cache["attn"] = nc
            else:
                c = cache.get("ssm") if cache else None
                out, nc = ssm_mod.ssm_apply(bp["ssm"], h, cfg, cache=c,
                                            chunk=run.ssm_chunk or None)
                if nc is not None:
                    new_cache["ssm"] = nc
            x = x + out

        if spec.cross and enc_out is not None:
            with jax.named_scope("attention"):
                h = rmsnorm(bp["ln_x"], x, cfg.norm_eps)
                out, _ = attn.gqa_apply(bp["xattn"], h, cfg, kv_src=enc_out,
                                        causal=False, use_rope=False,
                                        impl=self.attn_impl)
                x = x + out

        if spec.ffn == "dense":
            with jax.named_scope("mlp"):
                h = rmsnorm(bp["ln2"], x, cfg.norm_eps)
                x = x + mlp(bp["mlp"], h, cfg.mlp_type)
        elif spec.ffn == "moe":
            with jax.named_scope("moe"):
                h = rmsnorm(bp["ln2"], x, cfg.norm_eps)
                y, a = moe_mod.moe_apply(bp["moe"], h, cfg, mesh=self.mesh,
                                         dp_axes=self.dp_axes,
                                         combine=run.moe_combine)
                x = x + y
                aux = aux + a
        # §Perf iter 5: pin the block output while it is still bf16 so
        # the TP partial-sum all-reduce runs on the bf16 residual rather
        # than sinking past the next layer's fp32 norm upcast.
        if self.mesh is not None and cache is None and x.ndim == 3:
            from jax.sharding import NamedSharding, PartitionSpec as P
            if x.shape[0] % self.dp_size() == 0:
                x = jax.lax.with_sharding_constraint(
                    x, NamedSharding(self.mesh,
                                     P(self.dp_axes, None, None)))
        return x, aux, new_cache

    def _grad_sync_fn(self):
        """MXDAG-planned layer-wise gradient sync (repro/sync/overlap)."""
        if self.mesh is None or self.run.sync_mode != "bucketed":
            return None
        if getattr(self, "_sync_cache", None) is None:
            from repro.sync.overlap import make_grad_sync_fn
            self._sync_cache = make_grad_sync_fn(
                self.mesh, self.cfg, self.run, self.dp_axes)
        return self._sync_cache

    def _run_segments(self, segments, seg_params, x, *, positions=None,
                      caches=None, cache_index=None, enc_out=None):
        """Scan each segment over its repeats.  Returns (x, aux, caches).

        Training path with ``sync_mode="bucketed"``: the scan is replaced
        by the custom-vjp synced scan whose backward emits each layer's
        gradient reduce-scatter inside the reverse loop (Fig. 6 realized;
        see repro/sync/overlap.py).  ``"barrier"`` keeps the plain scan:
        XLA then reduces the stacked grads once after the loop — the
        coflow-like baseline.
        """
        total_aux = jnp.zeros((), jnp.float32)
        new_caches = []
        sync = self._grad_sync_fn() if caches is None else None
        if sync is not None:
            from repro.sync.overlap import make_synced_scan
            for si, seg in enumerate(segments):
                def body2(bps, xc, seg=seg):
                    aux = jnp.zeros((), jnp.float32)
                    for j, spec in enumerate(seg.pattern):
                        xc, a, _ = self._apply_block(
                            bps[j], spec, xc, positions=positions,
                            enc_out=enc_out)
                        aux = aux + a
                    return xc, aux

                scan_fn = make_synced_scan(body2, sync)
                x, aux_seg = scan_fn(seg_params[si], x)
                total_aux = total_aux + aux_seg
                new_caches.append(None)
            return x, total_aux, new_caches
        for si, seg in enumerate(segments):
            params_stack = seg_params[si]
            cache_stack = caches[si] if caches is not None else None

            def body(carry, xs, seg=seg):
                xc, auxc = carry
                bps, cs = xs
                ncs = []
                for j, spec in enumerate(seg.pattern):
                    xc, a, nc = self._apply_block(
                        bps[j], spec, xc, positions=positions,
                        cache=cs[j] if cs is not None else None,
                        cache_index=cache_index, enc_out=enc_out)
                    auxc = auxc + a
                    ncs.append(nc)
                return (xc, auxc), ncs

            if self.run.remat:
                body = self._remat(body, seg, x) if caches is None \
                    else jax.checkpoint(body)
            (x, total_aux), nc_stack = jax.lax.scan(
                body, (x, total_aux),
                (params_stack,
                 cache_stack if cache_stack is not None
                 else [None] * len(seg.pattern)))
            new_caches.append(nc_stack)
        return x, total_aux, new_caches

    def _remat(self, body, seg: Segment, x):
        """``body`` under ``jax.checkpoint``, keeping the residuals of the
        ``saved_tiers`` that ``seg`` has (tallied in ``REMAT``)."""
        have = self.tier_bytes(seg, x.shape[0] // self.dp_size(),
                               x.shape[1])
        kept = [t for t in self.saved_tiers if have[t]]
        if not kept:
            return jax.checkpoint(body)
        REMAT[",".join(kept)] += sum(have[t] for t in kept)
        names = [n for t in kept for n in REMAT_TIERS[t]]
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                *names))

    # ------------------------------------------------------------------
    def _encode(self, params, batch):
        """Whisper encoder over precomputed frame embeddings (stub)."""
        cfg = self.cfg
        x = batch["audio_embeds"].astype(self.dtype)
        espec = self.enc_segments[0].pattern[0]

        def body(carry, bp):
            xc, = carry
            xc, _, _ = self._apply_block(bp, espec, xc)
            return (xc,), None

        b = jax.checkpoint(body) if self.run.remat else body
        (x,), _ = jax.lax.scan(b, (x,), params["encoder"])
        return rmsnorm(params["enc_norm"], x, cfg.norm_eps)

    @jax.named_scope("embed")
    def _embed_inputs(self, params, batch):
        """Token (+ modality prefix) embedding.  Returns (x, n_prefix)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.dtype)
        n_prefix = 0
        if cfg.vision_embed_dim and "vision_embeds" in batch:
            v = batch["vision_embeds"].astype(self.dtype) @ params["vis_proj"]
            x = jnp.concatenate([v, x], axis=1)
            n_prefix = v.shape[1]
        if self.run.seq_shard and self.mesh is not None \
                and x.shape[1] % self.mesh.shape.get("model", 1) == 0:
            # sequence parallelism over the unused "model" axis (§Perf
            # mamba2 follow-up): pointwise projections, the conv (halo via
            # collective-permute) and the chunk-parallel SSD intra terms
            # all shard over seq; only the tiny inter-chunk state scan
            # crosses shards.
            from jax.sharding import NamedSharding, PartitionSpec as P
            dp = tuple(a for a in self.dp_axes if a != "model")
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh,
                                 P(dp if dp else None, "model", None)))
        return x, n_prefix

    def _tp(self) -> int:
        return self.mesh.shape.get("model", 1) if self.mesh is not None \
            else 1

    def _vocab_padded(self) -> int:
        # §Perf internvl2 iter 3: pad the LM head to a TP multiple so the
        # head stays vocab-sharded for odd vocabs (92553 -> 92560 @tp=16)
        # instead of replicating (iter 2's local contraction doubled head
        # flops) or all-reducing [B,S,V] logits (baseline).
        tp = self._tp()
        v = self.cfg.vocab_size
        return -(-v // tp) * tp

    def _vocab_sharded(self) -> bool:
        return True     # padding guarantees divisibility

    @jax.named_scope("head")
    def _head(self, params, x):
        cfg = self.cfg
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed"].T
        else:
            logits = x @ params["lm_head"]
        vp = logits.shape[-1]
        if vp != cfg.vocab_size:
            # mask padded vocab columns (elementwise; partitions cleanly)
            neg = jnp.where(jnp.arange(vp) < cfg.vocab_size,
                            0.0, -1e30).astype(logits.dtype)
            logits = logits + neg
        return logits

    # ------------------------------------------------------------------
    def forward(self, params, batch) -> jax.Array:
        enc_out = self._encode(params, batch) if self.cfg.encoder_layers \
            else None
        x, n_prefix = self._embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        x, aux, _ = self._run_segments(self.segments, params["segments"], x,
                                       positions=positions, enc_out=enc_out)
        return self._head(params, x)

    def loss(self, params, batch) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        enc_out = self._encode(params, batch) if cfg.encoder_layers else None
        x, n_prefix = self._embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        x, aux, _ = self._run_segments(self.segments, params["segments"], x,
                                       positions=positions, enc_out=enc_out)
        tokens = batch["tokens"]
        h = x[:, n_prefix:]                       # text region only
        logits = self._head(params, h[:, :-1])
        if self.run.logits_fp32:
            logits = logits.astype(jnp.float32)
        with jax.named_scope("loss"):
            ce = cross_entropy(logits, tokens[:, 1:],
                               vocab_sharded=self._vocab_sharded())
        loss = ce + cfg.router_aux_weight * aux
        metrics = {"ce": ce, "aux": aux}
        if cfg.mtp:
            mtp = params["mtp"]
            # predict t+2 from [h_t ; emb(t_{+1})] through one extra block
            h_in = rmsnorm(mtp["ln"], h[:, :-1], cfg.norm_eps)
            nxt = jnp.take(params["embed"], tokens[:, 1:], axis=0
                           ).astype(self.dtype)
            z = jnp.concatenate([h_in, nxt], axis=-1) @ mtp["proj"]
            z, _, _ = self._apply_block(mtp["block"],
                                        BlockSpec("attn", "dense"), z,
                                        positions=positions[: z.shape[1]])
            mtp_logits = self._head(params, z[:, :-1])
            with jax.named_scope("loss"):
                mtp_ce = cross_entropy(mtp_logits.astype(jnp.float32),
                                       tokens[:, 2:],
                                       vocab_sharded=self._vocab_sharded())
            loss = loss + 0.3 * mtp_ce
            metrics["mtp_ce"] = mtp_ce
        metrics["loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def init_cache(self, batch_size: int, max_len: int) -> Params:
        cfg = self.cfg
        caches = []
        for seg in self.segments:
            seg_caches = []
            for spec in seg.pattern:
                c: Params = {}
                if spec.mixer == "attn":
                    if cfg.attn_type == "mla":
                        one = attn.mla_cache_init(cfg, batch_size, max_len,
                                                  dtype=self.dtype)
                    else:
                        one = attn.gqa_cache_init(cfg, batch_size, max_len,
                                                  dtype=self.dtype)
                    c["attn"] = one
                else:
                    c["ssm"] = ssm_mod.ssm_cache_init(cfg, batch_size)
                seg_caches.append(jax.tree.map(
                    lambda a, R=seg.repeats: jnp.zeros(
                        (R,) + a.shape, a.dtype), c))
            caches.append(seg_caches)
        return caches

    def decode_step(self, params, caches, tokens, index, *,
                    enc_out=None):
        """One token step.  tokens: [B,1]; index: scalar int32 position."""
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.dtype)
        x, _, new_caches = self._run_segments(
            self.segments, params["segments"], x,
            caches=caches, cache_index=index, enc_out=enc_out)
        return self._head(params, x), new_caches
