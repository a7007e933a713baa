"""The traffic generator: every seed the same work, in another order."""

import numpy as np
import pytest

from bench import gen

MIX = {"request_events": 256, "rate_events_per_s": 25600.0,
       "tenant_shares": {"free": 1, "pro": 3, "enterprise": 4}}
DEP = {"vocab": 500, "zipf_exponent": 1.2}


def test_every_seed_gets_the_same_sizes_and_gaps():
    a = gen.requests(MIX, DEP, 10.0, 1)
    b = gen.requests(MIX, DEP, 10.0, 2**31 + 11)
    assert a.words.shape == b.words.shape == (1000, 256)
    def gaps(r):     # the gap after the last request runs to the close
        return np.sort(np.append(np.diff(r.due), 10.0 - r.due[-1]))

    np.testing.assert_allclose(gaps(a), gaps(b), rtol=0, atol=1e-9)
    assert not np.array_equal(a.due, b.due)
    assert sorted(a.tenant) == sorted(b.tenant)
    assert a.tenant.count("enterprise") == 500
    assert 0.0 == a.due[0] < a.due[-1] < 10.0


def test_same_seed_same_requests():
    a = gen.requests(MIX, DEP, 2.0, 7)
    b = gen.requests(MIX, DEP, 2.0, 7)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.words, b.words)


def test_wrapped_zipf_matches_numpy():
    p = gen.wrapped_zipf_pmf(1.2, 500)
    z = (np.random.default_rng(0).zipf(1.2, 400_000) - 1) % 500
    np.testing.assert_allclose(np.bincount(z, minlength=500) / 4e5, p,
                               atol=3e-3)
    rng = np.random.default_rng(1)
    words = gen.sample(rng, gen.alias_table(p), 400_000)
    np.testing.assert_allclose(np.bincount(words, minlength=500) / 4e5, p,
                               atol=3e-3)


def test_bursts_keep_the_mean_rate():
    rng = np.random.default_rng(0)
    due = gen.arrivals(rng, 2000, 10.0, {"every_s": 5.0, "length_s": 2.0,
                                          "factor": 2.0})
    counts = np.histogram(due, bins=10, range=(0, 10))[0]
    assert len(due) == 2000
    # 2 s at twice the rate of the other 3 s of every 5 s
    assert counts[:2].sum() == pytest.approx(2 * counts[2:4].sum(), rel=0.15)
