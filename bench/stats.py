"""Latency arithmetic over a run's request results.

Percentiles interpolate linearly between order statistics, as
``np.percentile`` does by default (the arithmetic of the scheduler's own
``summarize``).  A result is anything with the ``RequestResult`` fields
``status``, ``n_new``, ``arrival_s``, ``first_token_s`` and
``finish_s`` (seconds since the window opened; ``arrival_s`` is the
scheduled arrival, so a late generator counts against the system).
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0-100), or None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ok(results) -> list:
    return [r for r in results if r.status == "ok"]


def ttft_s(results) -> list:
    """First-token time minus scheduled arrival, per completed request."""
    return [r.first_token_s - r.arrival_s for r in ok(results)]


def tpot_s(results) -> list:
    """Mean gap between output tokens after the first, per completed
    request with at least two tokens."""
    return [(r.finish_s - r.first_token_s) / (r.n_new - 1)
            for r in ok(results) if r.n_new > 1]
