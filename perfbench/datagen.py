"""Seeded federation of token streams, in the shape of the program's
``make_lm_clients`` (per-client biased-unigram streams: a Dirichlet(0.5)
unigram per client over the whole vocabulary, one sequence per sample).

Every client holds exactly ``batches_per_client`` batches of
``batch_size`` x ``seq_len`` tokens, so every seed gives the same sizes and
only the tokens differ.  Returns plain numpy batches; the harness wraps them
in the program's client-data type.
"""
from __future__ import annotations

import numpy as np


def token_streams(seed: int, clients: int, vocab: int, seq_len: int,
                  batch_size: int, batches_per_client: int,
                  alpha: float = 0.5) -> dict:
    """client id -> list of {"inputs", "labels"} int32 batches."""
    rng = np.random.default_rng(int(seed))
    n = batch_size * batches_per_client
    out = {}
    for c in range(clients):
        bias = rng.dirichlet(np.full(vocab, alpha))
        toks = rng.choice(vocab, size=(n, seq_len + 1), p=bias)
        out[c] = [{"inputs": toks[i:i + batch_size, :-1].astype(np.int32),
                   "labels": toks[i:i + batch_size, 1:].astype(np.int32)}
                  for i in range(0, n, batch_size)]
    return out


def cohorts(seed: int, clients: int, per_round: int, rounds: int) -> list:
    """The clients each round samples: uniformly without replacement from a
    generator seeded with ``seed`` (FedAvg's client sampling)."""
    rng = np.random.default_rng(int(seed))
    return [[int(c) for c in rng.choice(np.arange(clients), size=per_round,
                                        replace=False)]
            for _ in range(rounds)]
