"""The benchmark's token stream: seeded zipf tokens with planted bigrams.

A copy of the program's ``repro.data.synthetic.token_batch``, kept here so
that a change to the program cannot change the traffic. Row ``r`` of step
``i`` is a pure function of ``(seed, i)``: every step and every row differ,
and the same seed gives the same stream.
"""
from __future__ import annotations

import numpy as np


class TokenStream:
    """Batches of ``batch`` rows of ``seq_len`` tokens with next-token labels.

    Half the positions are zipf noise over the vocabulary; the other half
    follow a fixed random bigram table, so a language model has something to
    learn and its loss falls.
    """

    def __init__(self, vocab: int, batch: int, seq_len: int, seed: int,
                 zipf_a: float):
        self.vocab, self.batch, self.seq_len = vocab, batch, seq_len
        self.seed, self.zipf_a = seed, zipf_a
        self.table = np.random.default_rng(seed).permutation(vocab)

    def __call__(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, step))
        ranks = rng.zipf(self.zipf_a, size=(self.batch, self.seq_len + 1))
        toks = np.minimum(ranks - 1, self.vocab - 1).astype(np.int32)
        # every odd position is the bigram successor of the token before it
        nxt = self.table[toks[:, :-1]]
        odd = (np.arange(self.seq_len)[None, :] % 2) == 1
        full = np.concatenate(
            [toks[:, :1], np.where(odd, nxt, toks[:, 1:])], axis=1)
        return {"tokens": full[:, :-1].astype(np.int32),
                "labels": full[:, 1:].astype(np.int32)}
