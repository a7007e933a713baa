"""The window arithmetic of the end-to-end metrics."""

import numpy as np
import pytest

from bench import window


def test_closed_loop_rate_runs_to_the_last_calls_end():
    # three calls of 100 records, the last ending 2.5 s after the start:
    # the window is extended to it, no call is cut
    assert window.closed_loop_rate(10.0, [11.0, 12.0, 12.5], 100) \
        == pytest.approx(300 / 2.5)


def test_closed_loop_rate_needs_a_call():
    with pytest.raises(ValueError):
        window.closed_loop_rate(0.0, [], 100)


def test_open_loop_rate_runs_to_the_last_delivery():
    # the last of three requests is delivered 4 s after the start
    assert window.open_loop_rate(1.0, [2.0, 5.0, 3.0], [10, 10, 20]) \
        == pytest.approx(40 / 4.0)


def test_p95_is_over_every_request_from_its_due_time():
    due = np.arange(100) * 0.1
    delivered = due + np.linspace(0.01, 1.0, 100)   # 10 ms .. 1000 ms
    assert window.latency_ms(due, delivered, 95) == pytest.approx(
        float(np.percentile(np.linspace(10, 1000, 100), 95)))
    # a stall that delays the last 10 requests by 5 s moves the tail
    late = delivered.copy()
    late[-10:] += 5.0
    assert window.latency_ms(due, late, 95) > 5000
