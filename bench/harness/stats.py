"""Percentiles, rates and spreads: the benchmark's own arithmetic."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile (0 < q <= 100): the smallest
    value with at least q% of the sample at or below it.  No
    interpolation, so the number is one that was measured."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])


def ttft_values(reqs, t_close: float) -> List[float]:
    """Seconds from each request's due time to its first TOKENS or DONE
    frame.  A request that failed, or that has no first frame by
    ``t_close``, counts as a miss: its value is the whole wait up to
    ``t_close``, longer than any request that was served."""
    out = []
    for r in reqs:
        res = r.res
        served = r.ok and res.t_first is not None
        out.append((res.t_first if served else max(t_close, r.due))
                   - r.due)
    return out


def tpot_values(reqs) -> List[float]:
    """Seconds per output token after the first, of each request that
    completed with two or more tokens: (t_done - t_first) / (n - 1)."""
    out = []
    for r in reqs:
        if not r.ok:
            continue
        n = len(r.res.tokens)
        if n >= 2:
            out.append((r.res.t_done - r.res.t_first) / (n - 1))
    return out


def tokens_between(before: dict, after: dict) -> int:
    """Output tokens that reached clients between two snapshots of
    ``{id(req): tokens held}``."""
    return sum(n - before.get(k, 0) for k, n in after.items())


def spread(values: Iterable[float]) -> float:
    """(third quartile - first quartile) / median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    vals = list(values)
    q1, med, q3 = statistics.quantiles(vals, n=4)
    return (q3 - q1) / med
