"""Token-serving traffic from a traffic file's parameters and a seed.

A mix is a closed loop of ``clients``: each submits its next request when
the last one has finished. Client i joins ``i × join_spacing_s`` into the
window, so that the window does not open with every prompt's prefill in one
tick. The lengths are one fixed multiset of ``requests`` (prompt, output)
pairs: prompt and output lengths each at the ``requests`` quantiles
(k + ½) ÷ ``requests`` of a log-normal (``median``, ``sigma``), so that the
set's medians are the file's, rounded and clipped to ``[min, max]``; the
outputs are paired with the prompts in the order of a permutation drawn
from the file's ``lengths_seed``, and a prompt is cut so that prompt +
output ≤ ``total_max``, as a service truncates a conversation's history to
its context. Every run serves that same set, so that seeds change the order
and the tokens and not the work: ``--seed`` permutes the set and deals it
to the clients in turn (each client then cycles through its share), and
draws the prompts' token ids uniformly over the vocabulary.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

from frames import seed_rng


def _lognormal(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def lengths(traffic: dict) -> list[tuple[int, int]]:
    """The mix's (prompt, output) lengths, the same for every seed."""
    n = traffic["requests"]
    prompts = _lognormal(traffic["prompt"], n)
    outputs = seed_rng(traffic["lengths_seed"]).permutation(_lognormal(traffic["output"], n))
    prompts = np.minimum(prompts, traffic["total_max"] - outputs)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


class Client:
    """One client's requests: its share of the lengths in the seed's order,
    with fresh token ids each time it comes round."""

    def __init__(self, traffic: dict, vocab: int, seed: int, index: int):
        pairs = lengths(traffic)
        order = seed_rng(seed, 1).permutation(len(pairs))
        self.pairs = [pairs[k] for k in order[index::traffic["clients"]]]
        self.rng = seed_rng(seed, 2, index)
        self.vocab = vocab
        self.join_s = index * traffic["join_spacing_s"]
        self.sent = 0

    def next(self) -> tuple[list, int]:
        """(prompt token ids, output length) of the next request."""
        plen, olen = self.pairs[self.sent % len(self.pairs)]
        self.sent += 1
        return self.rng.integers(0, self.vocab, plen).tolist(), olen
