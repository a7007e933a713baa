"""The arithmetic of a measured window.

Every end-to-end number is taken over all the work and all the time of the
window: a closed loop's rate over the time to the end of its last call (the
window is extended to that call's end, so no call is cut), an open loop's
rate over the time to the delivery of the last request that was due in the
window, and its tail over every request due in it, from its due time.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


def closed_loop_rate(start: float, call_ends: Sequence[float],
                     items_per_call: int) -> float:
    """Items of every completed call over the time from ``start`` to the
    end of the last call."""
    if not call_ends:
        raise ValueError("no call completed in the window")
    return len(call_ends) * items_per_call / (call_ends[-1] - start)


def open_loop_rate(start: float, delivered_at: Sequence[float],
                   items: Sequence[int]) -> float:
    """Items of every request due in the window over the time from
    ``start`` to the delivery of the last of them."""
    if len(delivered_at) == 0:
        raise ValueError("no request was due in the window")
    return float(np.sum(items)) / (float(np.max(delivered_at)) - start)


def latency_ms(due: Sequence[float], delivered_at: Sequence[float],
               q: float) -> float:
    """The ``q``-th percentile of delivery minus due time, in ms, over all
    requests (numpy's linear interpolation)."""
    lat = (np.asarray(delivered_at) - np.asarray(due)) * 1e3
    return float(np.percentile(lat, q))
