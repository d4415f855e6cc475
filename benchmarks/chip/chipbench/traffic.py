"""Training traffic from a seed: token batches for one cell.

A traffic file (``traffic/<name>.json``) names a generator and its
parameters, the batch per chip and the sequence length. The generators
are copies of the repository's synthetic streams, kept here so that the
yardstick does not move with the program:

* ``bigram``: tokens from a random bigram chain whose transition rows
  have Gumbel logits divided by ``concentration`` (a learnable stream);
* ``zipf``: Zipf(``a``) ranks over the vocabulary through a seed-stable
  permutation (the marginals of natural text).

The batches themselves come from the traffic file's own ``world_seed``,
so that every run trains on the same set of ``pool`` distinct batches
and the same language; the run's seed sets only their order. The work a
step does (how long the threshold search runs) follows the data, and a
seed that changed the data would change the work. A run draws the pool
at set-up and cycles through it.
"""
from __future__ import annotations

import numpy as np


def bigram_transition(vocab: int, seed: int,
                      concentration: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    logits = rng.gumbel(size=(vocab, vocab)) / concentration
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    return p / p.sum(axis=1, keepdims=True)


def bigram(vocab: int, batch: int, seq: int, seed: int, n: int,
           concentration: float = 0.3) -> list[np.ndarray]:
    cum = np.cumsum(bigram_transition(vocab, seed, concentration), axis=1)
    out = []
    for i in range(n):
        rng = np.random.default_rng((seed, i))
        toks = np.empty((batch, seq), np.int32)
        toks[:, 0] = rng.integers(0, vocab, size=batch)
        u = rng.random((batch, seq))
        for t in range(1, seq):
            toks[:, t] = (u[:, t, None] < cum[toks[:, t - 1]]).argmax(axis=1)
        out.append(toks)
    return out


def zipf(vocab: int, batch: int, seq: int, seed: int, n: int,
         a: float = 1.2) -> list[np.ndarray]:
    perm = np.random.default_rng(seed).permutation(vocab)
    out = []
    for i in range(n):
        rng = np.random.default_rng((seed, i))
        ranks = np.clip(rng.zipf(a, size=(batch, seq)), 1, vocab) - 1
        out.append(perm[ranks].astype(np.int32))
    return out


GENERATORS = {"bigram": bigram, "zipf": zipf}


def pool(traffic: dict, vocab: int, chips: int, seed: int) -> list[dict]:
    """The run's batches: ``traffic["pool"]`` global batches of
    ``batch_per_chip * chips`` rows, in the order ``seed`` gives them."""
    params = dict(traffic.get("params", {}))
    gen = GENERATORS[traffic["generator"]]
    toks = gen(vocab, traffic["batch_per_chip"] * chips, traffic["seq"],
               int(traffic["world_seed"]), traffic["pool"], **params)
    order = np.random.default_rng(int(seed)).permutation(len(toks))
    return [{"tokens": toks[i]} for i in order]


def cycle(batches: list[dict]):
    i = 0
    while True:
        yield batches[i % len(batches)]
        i += 1
