"""Plain reference of the terasort_100b deployment: a host sort in numpy.

The output is right when it holds every input record exactly once, in the
order of a stable host sort by key, each payload riding with its key. The
row id in the first four payload bytes names the input record (gensort's
row id). Imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def sort(keys: np.ndarray, payload: np.ndarray):
    """The deployment's answer: records in the order of a stable sort by
    key."""
    order = np.argsort(keys, kind="stable")
    return keys[order], payload[order]


def compare(keys_in: np.ndarray, payload_in: np.ndarray,
            keys_out: np.ndarray, payload_out: np.ndarray) -> Dict[str, int]:
    """Counts of what is wrong with one output (each 0 when right):

    - ``records_lost_or_extra``: |records out - records in|;
    - ``keys_out_of_order``: positions whose key differs from the host
      sort's key there (every position, if the counts differ);
    - ``rowids_not_once``: input records not delivered exactly once;
    - ``records_not_intact``: output rows whose key or payload differs from
      the input record their row id names.
    """
    n = keys_in.shape[0]
    m = keys_out.shape[0]
    want_keys, _ = sort(keys_in, payload_in[:, :4])
    out_of_order = (int(np.count_nonzero(keys_out != want_keys))
                    if m == n else n)
    rowid = np.ascontiguousarray(payload_out[:, :4]).view("<u4").ravel()
    in_range = rowid < n
    seen = np.bincount(rowid[in_range].astype(np.int64), minlength=n)
    not_once = int(np.count_nonzero(seen != 1))
    src = np.where(in_range, rowid, 0).astype(np.int64)
    intact = (in_range & (keys_in[src] == keys_out)
              & np.all(payload_in[src] == payload_out, axis=1))
    return {"records_lost_or_extra": abs(m - n),
            "keys_out_of_order": out_of_order,
            "rowids_not_once": not_once,
            "records_not_intact": int(np.count_nonzero(~intact))}
