"""Percentile and rate arithmetic — the benchmark's own, so that no PR
which claims a gain can change how a number is made."""

from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence

MISS = math.inf     # a failed, shed or unfinished request's latency


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (the smallest value with at least ``q``
    of the sample at or below it). ``MISS`` entries rank above every
    finished value; the caller decides what stands in for one."""
    xs = sorted(values)
    if not xs:
        return None
    if not 0 < q <= 1:
        raise ValueError(f"percentile wants 0 < q <= 1, got {q}")
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def tail_or_limit(values: Sequence[float], q: float,
                  limit: float) -> Optional[float]:
    """The percentile of all requests, misses included; where it falls
    on a miss the limit stands in its place (never infinity, never 0)."""
    p = percentile(values, q)
    if p is None:
        return None
    return limit if p == MISS else p


def samples_beyond(n: int, q: float) -> int:
    """How many samples lie above the nearest-rank percentile."""
    return n - max(1, math.ceil(q * n)) if n else 0


def tpot_ms(first_token_t: Optional[float], finish_t: Optional[float],
            tokens: int) -> Optional[float]:
    """Time per output token after the first, in ms; None where the
    request emitted fewer than two tokens or never finished."""
    if first_token_t is None or finish_t is None or tokens < 2:
        return None
    return (finish_t - first_token_t) * 1e3 / (tokens - 1)


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds


def mean(values: Iterable[float]) -> Optional[float]:
    xs = list(values)
    return sum(xs) / len(xs) if xs else None


def iqr_share(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median, by
    ``statistics.quantiles(values, n=4)`` — the spread the bounds are
    set from."""
    import statistics
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
