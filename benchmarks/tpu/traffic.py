"""Token batches of a training mix, made from the seed.

The stream is the one ``repro.data.SyntheticLM`` makes, copied here so
that the benchmark's inputs cannot change with the program: each row is
an affine progression ``(phase + stride * t) mod V`` with a share of
noise tokens, drawn from ``numpy.random.default_rng((seed, step))``.
Every seed gives the same sizes; only the rows differ.
"""
from __future__ import annotations

import numpy as np


def host_batch(seed: int, step: int, *, batch: int, seq: int, vocab: int,
               noise_prob: float) -> np.ndarray:
    """Rows ``[batch, seq]`` of int32 token ids for ``step``."""
    rng = np.random.default_rng((seed, step))
    phase = rng.integers(0, vocab, size=(batch, 1))
    stride = rng.integers(1, min(vocab - 1, 64), size=(batch, 1))
    t = np.arange(seq)[None, :]
    toks = (phase + stride * t) % vocab
    noise = rng.random((batch, seq)) < noise_prob
    toks = np.where(noise, rng.integers(0, vocab, size=(batch, seq)), toks)
    return toks.astype(np.int32)


def mix_batch(mix: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """The batch of ``step`` under a mix file's sizes.  ``vocab`` is the
    model's; the mix may draw from its first ``mix["vocab"]`` ids."""
    return host_batch(seed, step, batch=mix["batch"], seq=mix["seq"],
                      vocab=mix.get("vocab") or vocab,
                      noise_prob=mix["noise_prob"])
