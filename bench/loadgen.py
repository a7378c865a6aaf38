"""The one generator every traffic file feeds.

A serving mix draws prompts from a few fixed lengths with stated weights
and output lengths from a clipped lognormal.  The sizes come as blocks:
each block of ``block`` requests holds the same stratified set of prompt
and output lengths, in an order fixed by the mix alone; the seed draws the
token ids.  So every seed puts the same work into a window of any length:
a window that ends inside a block would otherwise hold a different share
of long prompts for each seed.  A training job draws its token rows from
the seed and the step, so no two rows repeat.
"""
from __future__ import annotations

import math

import numpy as np


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *salt])


def _lognormal_quantiles(median: float, sigma: float, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 1/2)/n of a lognormal."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return median * np.exp(sigma * z)


def block_sizes(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths) of one block, in a fixed order."""
    n = int(mix["block"])
    lens, weights = mix["prompt_lens"], mix["prompt_weights"]
    counts = np.floor(np.asarray(weights) * n + 0.5).astype(int)
    counts[-1] = n - counts[:-1].sum()
    prompts = np.repeat(np.asarray(lens, np.int64), counts)
    out = mix["output"]
    outs = np.clip(np.rint(_lognormal_quantiles(out["median"], out["sigma"],
                                                n)),
                   out["min"], out["max"]).astype(np.int64)
    return prompts, outs


def serve_requests(mix: dict, vocab: int, seed: int) -> list[dict]:
    """``mix["requests"]`` requests: prompt (int32 ids) and output length."""
    prompts, outs = block_sizes(mix)
    n_blocks = math.ceil(int(mix["requests"]) / len(prompts))
    order = np.random.default_rng(0)
    rng = rng_for(seed, 1)
    reqs = []
    for _ in range(n_blocks):
        p = order.permutation(prompts)
        o = order.permutation(outs)
        for L, m in zip(p, o):
            reqs.append({"prompt": rng.integers(0, vocab, int(L),
                                                dtype=np.int32),
                         "max_new": int(m)})
    return reqs[:int(mix["requests"])]


def train_rows(job: dict, vocab: int, seed: int, step: int,
               rows: int) -> np.ndarray:
    """(rows, seq + 1) int32 token ids of one step's global batch."""
    rng = rng_for(seed, 2, step)
    return rng.integers(0, vocab, (rows, int(job["seq_len"]) + 1),
                        dtype=np.int32)
