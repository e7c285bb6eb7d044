"""The traffic generator: bigram token streams, one table per client.

A vectorised copy of ``repro.data.synthetic.BigramLM`` (the original draws
every token with its own ``rng.choice`` call, which at 8,192 tokens a round
holds the chip up).  The tables are drawn exactly as the original draws
them, so ``BigramTable(vocab, s)`` and ``BigramLM(vocab, s)`` hold the same
``next_tokens`` and ``probs`` for the same seed.  Each client has a table
of its own, so the clients' data is not identically distributed.

A traffic mix (``bench/traffic/<mix>.json``) names this generator as
``"generator": "bigram"`` and gives the job's sizes: ``clients``,
``local_steps``, ``seqs_per_step``, ``seq_len`` and the table's
``branching``.  The harness takes the module's ``Feed``.
"""
from __future__ import annotations

import numpy as np


class BigramTable:
    """Sparse Markov chain over ``vocab`` tokens with ``branching``
    successors per token."""

    def __init__(self, vocab: int, seed: int, branching: int = 4):
        rng = np.random.default_rng(seed)
        self.vocab = vocab
        self.next_tokens = rng.integers(0, vocab, size=(vocab, branching))
        self.probs = rng.dirichlet(np.ones(branching), size=vocab)
        self._cdf = np.cumsum(self.probs, axis=1)

    def sample(self, rng: np.random.Generator, rows: int, seq: int):
        """``[rows, seq]`` int32 tokens; the first token of each row is
        uniform, every later one a successor of the one before it."""
        toks = np.empty((rows, seq), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, size=rows)
        u = rng.random((seq - 1, rows, 1))
        last = self.next_tokens.shape[1] - 1
        for t in range(1, seq):
            prev = toks[:, t - 1]
            choice = np.minimum((u[t - 1] > self._cdf[prev]).sum(axis=1),
                                last)
            toks[:, t] = self.next_tokens[prev, choice]
        return toks


def table_seed(seed: int, client: int) -> int:
    """Seed of client ``client``'s table under run seed ``seed``."""
    return int(np.random.SeedSequence([seed % 2**64, client])
               .generate_state(1)[0])


class BigramFeed:
    """Round batches ``{"tokens": [K, C, B, S] int32}`` (local step, client,
    sequence, position): client ``c`` draws from its own table.  The same
    ``seed`` gives the same tables and the same stream of batches."""

    def __init__(self, vocab: int, mix: dict, seed: int):
        self.shape = (mix["local_steps"], mix["clients"],
                      mix["seqs_per_step"], mix["seq_len"])
        branching = mix.get("branching", 4)
        self.tables = [BigramTable(vocab, table_seed(seed, c), branching)
                       for c in range(mix["clients"])]
        self.rng = np.random.default_rng([seed % 2**64, mix["clients"]])

    def next(self) -> dict:
        K, C, B, S = self.shape
        toks = np.empty(self.shape, np.int32)
        for c, table in enumerate(self.tables):
            toks[:, c] = table.sample(self.rng, K * B, S).reshape(K, B, S)
        return {"tokens": toks}


Feed = BigramFeed
