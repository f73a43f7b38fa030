"""Length and inter-arrival laws of the traffic files, by name.

Every law gives a stratified sample: the n values at the quantiles
(i + 0.5) / n of the law, so that two seeds draw the same multiset of
sizes and gaps and differ only in their order (the order, and the
token contents, come from the seed). A law is a dict from a traffic
file: {"law": name, ...parameters}.
"""
from __future__ import annotations

import numpy as np
from scipy import stats


def _uniform(q, lo, hi):
    return lo + q * (hi - lo)


def _loguniform(q, lo, hi):
    return np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))


LENGTH_LAWS = {"uniform": _uniform, "loguniform": _loguniform}


def _poisson(q, rate):
    return -np.log1p(-q) / rate


def _gamma(q, rate, cv):
    """Gamma renewal gaps with mean 1/rate and coefficient of variation
    cv (cv 1 is Poisson; BurstGPT fits cv near 2 for conversation
    traffic)."""
    shape = 1.0 / (cv * cv)
    return stats.gamma.ppf(q, shape, scale=1.0 / (rate * shape))


ARRIVAL_LAWS = {"poisson": _poisson, "gamma": _gamma}


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(law: dict, n: int) -> np.ndarray:
    """n stratified integer lengths, ascending."""
    p = {k: v for k, v in law.items() if k != "law"}
    vals = LENGTH_LAWS[law["law"]](quantiles(n), **p)
    return np.maximum(np.rint(vals), 1).astype(np.int64)


def gaps(law: dict, rate: float, n: int) -> np.ndarray:
    """n stratified inter-arrival gaps (seconds), ascending, rescaled to
    sum to exactly n / rate."""
    p = {k: v for k, v in law.items() if k != "law"}
    g = ARRIVAL_LAWS[law["law"]](quantiles(n), rate=rate, **p)
    return g * (n / rate) / g.sum()
