"""The arithmetic of the end-to-end metrics."""

from __future__ import annotations

import math


def window_rate(n_verdicts: int, t_start: float,
                t_last: float) -> float | None:
    """Verdicts per second over the whole window, which ends at the last
    verdict."""
    if n_verdicts <= 0 or t_last <= t_start:
        return None
    return n_verdicts / (t_last - t_start)


def p95_all(latencies: list[float | None]) -> float | None:
    """Nearest-rank 95th percentile over every request; a request with
    no verdict (``None``) counts as slower than any other, so a p95 that
    lands on one is undefined (``None``)."""
    if not latencies:
        return None
    ranked = sorted(latencies, key=lambda x: math.inf if x is None else x)
    v = ranked[math.ceil(0.95 * len(ranked)) - 1]
    return v


def ii_efficiency(pairs: list[tuple[int, int]]) -> float | None:
    """sum(MII) / sum(II) over the window's bindings ``(mii, ii)``."""
    if not pairs:
        return None
    return sum(m for m, _ in pairs) / sum(i for _, i in pairs)

