"""Seeded traffic: the training stream and the generation queue.

``MarkovStream`` is a copy of the program's synthetic stream
(``data/pipeline.py`` ``TokenPipeline.batch_at``), kept here so that the
reference sees the rows the program should have trained on without taking
them from the program. ``prompt_set`` gives every seed the same prompt
lengths in the same order, so the seed changes which tokens are served
and not how much work there is: with every prompt queued at once, the
order of the lengths decides how the slots pack, and so the job's length.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


class MarkovStream:
    """Order-1 Markov rows over ``vocab`` tokens, as the program draws them."""

    def __init__(self, seed: int, vocab: int, seq_len: int, batch: int,
                 temp: float):
        self.seed, self.vocab, self.seq_len, self.batch = \
            seed, vocab, seq_len, batch
        rng = np.random.default_rng(seed)
        logits = rng.normal(0, temp, (vocab, vocab))
        trans = np.exp(logits - logits.max(1, keepdims=True))
        trans /= trans.sum(1, keepdims=True)
        self.cdf = np.cumsum(trans, axis=1)

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, 0, step, 0xACA1))
        n, s = self.batch, self.seq_len
        rows = np.empty((n, s + 1), np.int32)
        rows[:, 0] = rng.integers(0, self.vocab, n)
        for t in range(s):
            u = rng.random(n)
            rows[:, t + 1] = (self.cdf[rows[:, t]] < u[:, None]).sum(1)
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


def prompt_lengths(n: int, median: float, sigma: float, lo: int,
                   hi: int) -> list[int]:
    """``n`` lognormal lengths at evenly spaced quantiles, clipped to
    [lo, hi]: the same set for every seed."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


ORDER_SEED = 0x9E7     # one shuffle of the lengths, for every seed


def prompt_set(seed: int, n: int, vocab: int, *, median: float,
               sigma: float, lo: int, hi: int) -> list[list[int]]:
    """``n`` prompts of token ids in [0, vocab): lengths from
    ``prompt_lengths`` in one fixed shuffled order, tokens drawn from the
    seed."""
    lengths = prompt_lengths(n, median, sigma, lo, hi)
    order = np.random.default_rng(ORDER_SEED).permutation(n)
    rng = np.random.default_rng((seed, ORDER_SEED))
    return [rng.integers(0, vocab, lengths[i]).tolist() for i in order]


def serve_schedule(lengths: list[int], slots: int, max_new: int):
    """Replay ``launch/serve.py``'s slot schedule on the host: each step
    every busy slot feeds one token; a slot whose request has produced
    ``max_new`` tokens takes the next one. Yields, per step, the cache
    length each busy slot attends over (its filled length after the
    insert). Used for the decode step's required bytes."""
    queue = list(lengths)
    nxt = 0
    left = [0] * slots          # tokens still to feed (prompt + outputs)
    filled = [0] * slots
    busy = [False] * slots

    def refill(s):
        nonlocal nxt
        if nxt < len(queue):
            left[s] = queue[nxt] + max_new - 1
            filled[s] = 0
            busy[s] = True
            nxt += 1
        else:
            busy[s] = False

    for s in range(slots):
        refill(s)
    while any(busy):
        live = []
        for s in range(slots):
            if not busy[s]:
                continue
            filled[s] += 1
            live.append(filled[s])
        yield live
        for s in range(slots):
            if not busy[s]:
                continue
            left[s] -= 1
            if left[s] == 0:
                refill(s)
