"""Plain reference of the terasort_100b_key10 deployment: TeraSort's order,
a host sort in numpy by all of the key's bytes.

The output is right when it holds every input record exactly once, in the
lexicographic (memcmp) order of the whole key — sortbenchmark.org's order
for gensort's 10-byte keys — each payload riding with its key. The row id
in the first four payload bytes names the input record (gensort's record
number, cut to 4 bytes). Imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def sort_order(keys: np.ndarray) -> np.ndarray:
    """Indices that sort ``(n, k)`` uint8 keys by all ``k`` bytes, memcmp
    order (not stable: equal keys may come in any order). The bytes,
    zero-padded to whole 8-byte words, are read as big-endian uint64
    columns; one sort by the first column, then the rows that tie on it
    are sorted again by every column."""
    n, k = keys.shape
    padded = np.zeros((n, -(-k // 8) * 8), np.uint8)
    padded[:, :k] = keys
    words = padded.view(">u8").astype(np.uint64)
    order = np.argsort(words[:, 0])
    if words.shape[1] > 1:
        lead = words[order, 0]
        same = lead[1:] == lead[:-1]
        tied = np.zeros(n, bool)
        tied[1:] |= same
        tied[:-1] |= same
        at = np.flatnonzero(tied)
        sub = order[at]
        order[at] = sub[np.lexsort(words[sub].T[::-1])]
    return order


def compare(keys_in: np.ndarray, payload_in: np.ndarray,
            keys_out: np.ndarray, payload_out: np.ndarray) -> Dict[str, int]:
    """Counts of what is wrong with one output (each 0 when right); keys
    are ``(n, k)`` uint8, payloads ``(n, p)`` uint8:

    - ``records_lost_or_extra``: |records out - records in|;
    - ``keys_out_of_order``: positions whose key differs, in any byte, from
      the host sort's key there (every position, if the counts differ);
    - ``rowids_not_once``: input records not delivered exactly once;
    - ``records_not_intact``: output rows whose key or payload differs from
      the input record their row id names.
    """
    n = keys_in.shape[0]
    m = keys_out.shape[0]
    want_keys = keys_in[sort_order(keys_in)]
    out_of_order = (int(np.count_nonzero(np.any(keys_out != want_keys,
                                                 axis=1)))
                    if m == n else n)
    rowid = np.ascontiguousarray(payload_out[:, :4]).view("<u4").ravel()
    in_range = rowid < n
    seen = np.bincount(rowid[in_range].astype(np.int64), minlength=n)
    not_once = int(np.count_nonzero(seen != 1))
    src = np.where(in_range, rowid, 0).astype(np.int64)
    intact = (in_range & np.all(keys_in[src] == keys_out, axis=1)
              & np.all(payload_in[src] == payload_out, axis=1))
    return {"records_lost_or_extra": abs(m - n),
            "keys_out_of_order": out_of_order,
            "rowids_not_once": not_once,
            "records_not_intact": int(np.count_nonzero(~intact))}
