"""The one traffic generator: token batches and request waves from a seed.

Tokens come from a fixed random bigram chain, so text has learnable
structure and every batch is a pure function of the seed.  (A copy of the
chain of the program's synthetic data pipeline, kept here so that the
yardstick does not move when the program changes.)

What a run generates is set by its traffic file (``traffic/<mix>.json``);
the seed changes only which tokens are drawn and, for serving, nothing of
the sizes: every seed gets the same prompt and output lengths.
"""
from __future__ import annotations

import statistics

import numpy as np


class BigramChain:
    """A random bigram chain over ``vocab`` tokens with ``branching``
    successors per token, drawn from ``chain_seed``."""

    def __init__(self, vocab: int, branching: int, chain_seed: int):
        rng = np.random.default_rng(chain_seed)
        b = min(branching, vocab)
        self.vocab = vocab
        self.succ = rng.integers(0, vocab, size=(vocab, b), dtype=np.int32)
        w = rng.dirichlet(np.ones(b) * 0.5, size=vocab).astype(np.float32)
        self.cum_w = np.cumsum(w, axis=1)

    def sample(self, rng: np.random.Generator, rows: int, length: int):
        """(rows, length) int32 token sequences."""
        toks = np.empty((rows, length), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, size=rows)
        u = rng.random((rows, length - 1), dtype=np.float32)
        for t in range(1, length):
            prev = toks[:, t - 1]
            idx = (self.cum_w[prev] < u[:, t - 1:t]).sum(axis=1)
            idx = np.minimum(idx, self.succ.shape[1] - 1)
            toks[:, t] = self.succ[prev, idx]
        return toks


def train_ring(traffic: dict, vocab: int, seed: int) -> list:
    """``ring`` distinct batches of (global_batch, seq_len + 1) tokens.
    Every row of the ring differs from every other."""
    chain = BigramChain(vocab, traffic["branching"], traffic["chain_seed"])
    rng = np.random.default_rng(seed)
    B, S, R = traffic["global_batch"], traffic["seq_len"], traffic["ring"]
    toks = chain.sample(rng, B * R, S + 1)
    if len({row.tobytes() for row in toks}) != B * R:
        raise RuntimeError("the batch ring repeats a row; draw again")
    return [toks[i * B:(i + 1) * B] for i in range(R)]


def wave_shapes(traffic: dict) -> list:
    """The (prompt_len, max_new_tokens) pairs of one wave, in order.  The
    prompt lengths take each of ``prompt_lens`` in turn; the output
    lengths are the lognormal's quantiles at (i + 1/2) / n, clipped, so a
    wave holds the distribution itself and not a draw from it.  They are
    paired in an order drawn from ``shape_seed`` alone: every run and
    every wave does the same work."""
    n = traffic["wave_requests"]
    prompts = np.resize(np.asarray(traffic["prompt_lens"]), n)
    out = traffic["output_len"]
    z = [statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    new = out["median"] * np.exp(out["sigma"] * np.asarray(z))
    new = np.clip(np.round(new), out["min"], out["max"]).astype(int)
    new = np.random.default_rng(traffic["shape_seed"]).permutation(new)
    return [(int(p), int(m)) for p, m in zip(prompts, new)]


def wave_prompts(traffic: dict, vocab: int, seed: int, wave: int) -> list:
    """Prompt token lists of wave number ``wave`` of a run with ``seed``."""
    chain = BigramChain(vocab, traffic["branching"], traffic["chain_seed"])
    rng = np.random.default_rng([seed, wave])
    return [chain.sample(rng, 1, p)[0] for p, _ in wave_shapes(traffic)]
