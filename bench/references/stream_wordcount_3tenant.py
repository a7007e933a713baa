"""Plain reference of the stream_wordcount_3tenant deployment: a count.

After the stream has served every request, its carried aggregate must hold
one row per word seen, with the number of times the word was submitted —
``np.bincount`` over every event of every request, each request counted
once. Imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def count(events: np.ndarray, vocab: int) -> np.ndarray:
    return np.bincount(events.astype(np.int64), minlength=vocab)


def compare(keys: np.ndarray, values: np.ndarray, events: np.ndarray,
            vocab: int) -> Dict[str, int]:
    """Counts of what is wrong with the carried aggregate (each 0 when
    right): ``carry_rows_duplicated``, rows beyond one per word;
    ``words_miscounted``, words whose carried count differs from the
    reference's (a key outside the vocabulary counts as one)."""
    keys = np.asarray(keys).astype(np.int64)
    values = np.asarray(values).astype(np.int64)
    dup = len(keys) - len(np.unique(keys))
    inside = (keys >= 0) & (keys < vocab)
    got = np.zeros(vocab, np.int64)
    np.add.at(got, keys[inside], values[inside])
    wrong = int(np.count_nonzero(got != count(events, vocab)))
    return {"carry_rows_duplicated": int(dup),
            "words_miscounted": wrong + int(np.count_nonzero(~inside))}
