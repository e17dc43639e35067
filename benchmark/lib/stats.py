"""Percentiles and rates over the readings of one run, unrounded."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def pct(values: Sequence[float], q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median — the driver's
    measure of run-to-run spread."""
    if len(values) < 2:
        return None
    v = np.asarray(values, np.float64)
    q1, q2, q3 = np.percentile(v, [25, 50, 75])
    return float((q3 - q1) / abs(q2)) if q2 else None


def prompt_tokens_between(prefills: Sequence[Tuple[int, float, float]],
                          lo: float, hi: float) -> float:
    """Prompt tokens prefilled inside [lo, hi): each request's prompt
    (tokens, submitted, first token) spread evenly between its submission
    and its first token, the only two instants a client can see.  Exact for
    a request wholly inside the window; at an edge it splits the prompt by
    time, so a window does not gain or lose a whole prompt by an instant."""
    total = 0.0
    for tokens, submitted, first in prefills:
        inside = min(first, hi) - max(submitted, lo)
        if inside > 0:
            total += tokens * inside / (first - submitted)
    return total
