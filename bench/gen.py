"""The one traffic generator: a mix file's parameters to requests.

A mix (``traffic/<mix>.json``) gives the offered rate, the request size,
the tenants' shares and, optionally, bursts. Every seed gets the same
number of requests, the same multiset of inter-arrival gaps (the quantiles
of an exponential distribution: Poisson arrivals, drawn in another order)
and the same count per tenant; the seed orders them and draws the words.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np
from scipy.special import zeta


def wrapped_zipf_pmf(a: float, vocab: int) -> np.ndarray:
    """Distribution of ``(Z - 1) % vocab`` for ``Z ~ Zipf(a)`` (numpy's
    ``rng.zipf``): P(k) = sum_m (k + 1 + m vocab)^-a / zeta(a)
    = vocab^-a zeta(a, (k + 1) / vocab) / zeta(a) (Hurwitz zeta)."""
    k = np.arange(vocab, dtype=np.float64)
    p = vocab ** -a * zeta(a, (k + 1) / vocab) / zeta(a, 1)
    return p / p.sum()


def alias_table(p: np.ndarray):
    """Vose's alias table of the distribution ``p``."""
    n = len(p)
    prob = np.zeros(n)
    alias = np.zeros(n, np.int32)
    scaled = p * n
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        prob[s], alias[s] = scaled[s], g
        scaled[g] -= 1.0 - scaled[s]
        (small if scaled[g] < 1.0 else large).append(g)
    for i in small + large:
        prob[i] = 1.0
    return prob, alias


def sample(rng: np.random.Generator, table, shape) -> np.ndarray:
    prob, alias = table
    idx = rng.integers(0, len(prob), size=shape, dtype=np.int32)
    return np.where(rng.random(shape, dtype=np.float32)
                    < prob.astype(np.float32)[idx], idx, alias[idx])


def arrivals(rng: np.random.Generator, count: int, seconds: float,
             bursts: Optional[Dict[str, float]] = None) -> np.ndarray:
    """``count`` due times in [0, seconds): exponential-quantile gaps in an
    order drawn from ``rng``. With ``bursts`` (``every_s``, ``length_s``,
    ``factor``) the rate is ``factor`` times higher for ``length_s`` at the
    start of every ``every_s``, and the mean rate is unchanged."""
    gaps = -np.log1p(-(np.arange(count) + 0.5) / count)
    gaps = rng.permutation(gaps)
    op = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    op *= seconds / gaps.sum()
    if not bursts:
        return op
    every, length, factor = (bursts["every_s"], bursts["length_s"],
                             bursts["factor"])
    t = np.linspace(0.0, seconds, int(seconds * 1000) + 1)
    weight = np.where(np.mod(t, every) < length, factor, 1.0)
    cum = np.concatenate([[0.0], np.cumsum((weight[1:] + weight[:-1]) / 2
                                           * np.diff(t))])
    cum *= seconds / cum[-1]
    return np.interp(op, cum, t)


def tenants(rng: np.random.Generator, count: int,
            shares: Dict[str, float]) -> List[str]:
    """``count`` tenant names in proportion to ``shares`` (largest
    remainder), in an order drawn from ``rng``."""
    names = list(shares)
    w = np.array([shares[n] for n in names], np.float64)
    exact = count * w / w.sum()
    n = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - n), kind="stable")[:count - n.sum()]:
        n[i] += 1
    seq = np.repeat(np.arange(len(names)), n)
    return [names[i] for i in rng.permutation(seq)]


@dataclasses.dataclass
class Requests:
    due: np.ndarray          # seconds after the window's start
    tenant: List[str]
    words: np.ndarray        # (count, request_events) uint16 word ids


def requests(mix: Dict[str, Any], deployment: Dict[str, Any],
             seconds: float, seed: int) -> Requests:
    """The requests due in a window of ``seconds`` at the mix's rate, with
    words drawn from the deployment's vocabulary and skew."""
    rng = np.random.default_rng(abs(int(seed)))
    size = mix["request_events"]
    count = max(1, int(round(mix["rate_events_per_s"] * seconds / size)))
    due = arrivals(rng, count, seconds, mix.get("bursts"))
    who = tenants(rng, count, mix["tenant_shares"])
    table = alias_table(wrapped_zipf_pmf(deployment["zipf_exponent"],
                                         deployment["vocab"]))
    words = sample(rng, table, (count, size)).astype(np.uint16)
    return Requests(due, who, words)
