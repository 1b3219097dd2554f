"""Seeded token rows, read by the launcher's ``tokens`` loader.

``<root>/tokens/train.npz`` holds ``x`` (int32 ``[clients * rows,
seq_len]``) and ``client`` (each row's client, sorted); ``test.npz``
holds ``x`` (``[test_rows, seq_len]``). Every token is drawn on its
own from its client's unigram law over the vocabulary slice: a Zipf
law (exponent 1.1) over the ranks, the ranks mapped to ids half the
time by the client's own seeded permutation and half the time by the
population's common one, so that the loss falls as the model learns
the frequencies and the clients differ from each other. Test rows come
from the common permutation alone. Ranks are drawn through a table of
2**20 equal steps of the law's cumulative distribution (two vectorised
passes a token, so that a store of 10**8 tokens is seconds of set-up;
a rank's probability is right to 2**-20). Every client has the same
number of rows, so no shape depends on the seed. A pure function of
``seed``.
"""
from __future__ import annotations

import os

import numpy as np

ZIPF_EXPONENT = 1.1
OWN_SHARE = 0.5
TABLE_BITS = 20
TABLE = 1 << TABLE_BITS


def rank_table(vocab: int) -> np.ndarray:
    """Rank at each of ``TABLE`` equal steps of the Zipf law's
    cumulative distribution: a uniform index into it draws a rank."""
    p = 1.0 / np.arange(1, vocab + 1) ** ZIPF_EXPONENT
    cdf = np.cumsum(p / p.sum())
    steps = (np.arange(TABLE) + 0.5) / TABLE
    return np.minimum(np.searchsorted(cdf, steps), vocab - 1).astype(
        np.int32)


def _rows(rng, table, maps, rows: int, seq_len: int):
    """``rows`` x ``seq_len`` ids from one uniform draw a token: its
    low 20 bits index the rank table, the next bit (under
    ``OWN_SHARE`` = 1/2) chooses which of the ``maps`` (rank -> id
    permutations laid end to end) turns the rank into an id."""
    draw = rng.integers(0, TABLE * len(maps), size=rows * seq_len,
                        dtype=np.int32)
    ranks = table[draw & (TABLE - 1)]
    ranks += (draw >> TABLE_BITS) * np.int32(len(maps[0]))
    return np.concatenate(maps)[ranks].reshape(rows, seq_len)


def write(root: str, seed: int, clients: int, rows_per_client: int,
          seq_len: int, vocab_size: int, test_rows: int) -> str:
    """Write the files under ``root`` and return the ``--data_dir``."""
    base = os.path.join(root, "tokens")
    os.makedirs(base, exist_ok=True)
    rng = np.random.default_rng(seed)
    table = rank_table(vocab_size)
    common = rng.permutation(vocab_size).astype(np.int32)
    assert OWN_SHARE == 0.5, "one bit of the draw is the coin"
    x = np.empty((clients * rows_per_client, seq_len), np.int32)
    for c in range(clients):
        own = rng.permutation(vocab_size).astype(np.int32)
        x[c * rows_per_client:(c + 1) * rows_per_client] = _rows(
            rng, table, (common, own), rows_per_client, seq_len)
    np.savez(os.path.join(base, "train.npz"), x=x,
             client=np.repeat(np.arange(clients, dtype=np.int32),
                              rows_per_client))
    np.savez(os.path.join(base, "test.npz"),
             x=_rows(rng, table, (common,), test_rows, seq_len))
    return root
