"""The general traffic generator: open-loop arrivals and request picks.

Every seed gets the same work in another order: the same number of
requests, the same multiset of inter-arrival gaps (the quantiles of an
exponential distribution at the mix's rate, so arrivals are Poisson-like)
and the same count of each variant (Zipf by popularity rank, rounded by
largest remainder); the seed shuffles the gaps and the order of requests.
"""
from __future__ import annotations

import numpy as np


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """Requests per popularity rank 1..k for n requests, Zipf exponent s."""
    p = np.arange(1, k + 1, dtype=np.float64) ** -s
    p /= p.sum()
    raw = p * n
    counts = np.floor(raw).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(-(raw - counts), kind="stable")[:rest]] += 1
    return counts


def exponential_gaps(n: int, rate: float, seconds: float) -> np.ndarray:
    """n inter-arrival gaps at the quantiles of Exp(rate), scaled so that
    they add up to the window."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return gaps * (seconds / gaps.sum())


def open_loop(seed: int, rate: float, seconds: float, num_variants: int,
              zipf_s: float):
    """→ (due times in s from the window's start (N,), variant index (N,)).
    Variant i has popularity rank i + 1.  Request i is due after the sum
    of the first i shuffled gaps."""
    n = int(round(rate * seconds))
    rng = np.random.default_rng(int(seed))
    gaps = rng.permutation(exponential_gaps(n, rate, seconds))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    picks = np.repeat(np.arange(num_variants),
                      zipf_counts(n, num_variants, zipf_s))
    return due, rng.permutation(picks)
