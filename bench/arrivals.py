"""The one traffic generator: an open-loop arrival schedule from a mix's
parameters and a seed.

A mix (``bench/traffic/<mix>.json``) with ``"driver": "open_loop"``
gives ``rate_per_s``.  Every seed gets the same set of inter-arrival
gaps -- drawn once from ``gap_seed`` and scaled so that
``rate_per_s * seconds`` requests span the window exactly -- in an order
the seed permutes, and its own choice of input members.  So two seeds
offer the same work at the same mean rate, and differ only in order and
data.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    """When each request is due (seconds from the window's start) and
    which input member it sends."""

    offsets_s: np.ndarray
    members: np.ndarray


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use of ``seed``: any whole number, with
    ``stream`` keeping the uses apart."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def schedule(mix: dict, seed: int, seconds: float, pool: int) -> Schedule:
    """The arrival schedule of one run of ``seconds``."""
    n = int(round(mix["rate_per_s"] * seconds))
    if n < 1:
        raise ValueError(f"rate {mix['rate_per_s']}/s gives no request in "
                         f"{seconds} s")
    gaps = np.random.default_rng(mix.get("gap_seed", 0)).exponential(1.0, n)
    rng = seed_rng(seed, 1)
    gaps = gaps[rng.permutation(n)]
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    offsets *= seconds / gaps.sum()
    members = rng.integers(0, pool, size=n)
    return Schedule(offsets_s=offsets, members=members)


def percentile(values, q: float) -> float:
    """Exact nearest-rank percentile: the smallest recorded value with at
    least ``q`` percent of the values at or below it."""
    if not values:
        raise ValueError("no values")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100], got {q}")
    ordered = sorted(values)
    rank = int(np.ceil(q / 100.0 * len(ordered)))
    return float(ordered[max(rank, 1) - 1])
