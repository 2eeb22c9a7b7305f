"""Serving step assembly: prefill + batched greedy decode.

``make_serve_step`` returns the single-token decode function the
decode/long-context dry-run cells lower; ``main`` is a smoke demo: it
serves the REDUCED config of ``--arch`` (batched requests, continuous
decode) with random weights, on whatever backend jax finds.
"""
from __future__ import annotations

import argparse
from typing import Optional

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import RunConfig
from repro.launch import compile_cache
from repro.launch.mesh import dp_axes, make_mesh
from repro.models import Model


def make_serve_step(model: Model):
    def serve_step(params, cache, tokens, index):
        logits, cache = model.decode_step(params, cache, tokens, index)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        return next_tok.astype(jnp.int32), cache
    return serve_step


def main(argv: Optional[list[str]] = None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="mamba2-130m")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen", type=int, default=32)
    args = p.parse_args(argv)

    compile_cache.enable()
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = configs.get_smoke(args.arch)
    model = Model(cfg, RunConfig(remat=False), mesh=mesh,
                  dp_axes=dp_axes(mesh))
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)

    B = args.batch
    max_len = args.prompt_len + args.gen
    cache = model.init_cache(B, max_len)
    prompts = jax.random.randint(rng, (B, args.prompt_len), 0,
                                 cfg.vocab_size)

    step = jax.jit(make_serve_step(model))
    # prefill token-by-token (simple; a fused prefill is the prefill cell)
    tok = prompts[:, :1]
    for t in range(args.prompt_len):
        tok, cache = step(params, cache, prompts[:, t:t + 1], jnp.int32(t))
    generated = [tok]
    for t in range(args.prompt_len, max_len - 1):
        tok, cache = step(params, cache, tok, jnp.int32(t))
        generated.append(tok)
    out = jnp.concatenate(generated, axis=1)
    print(f"served {B} requests, generated {out.shape[1]} tokens each")
    print("sample:", out[0, :16].tolist())


if __name__ == "__main__":
    main()
