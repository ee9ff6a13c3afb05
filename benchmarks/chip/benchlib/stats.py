"""Exact statistics over a run's per-request records (no sketches)."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile with linear interpolation between order
    statistics (numpy's default); None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_samples(records, t0: float, t1: float) -> list:
    """Seconds from due to first token of every request due in [t0, t1);
    one still without a token at t1 counts the wait it has had (t1 - due)."""
    out = []
    for r in records:
        if t0 <= r.due < t1:
            first = r.token_t[0] if r.token_t else None
            out.append((first if first is not None and first <= t1 else t1)
                       - r.due)
    return out


def itl_samples(records, t0: float, t1: float) -> list:
    """Every gap between consecutive output tokens of one request that
    ends inside [t0, t1]."""
    out = []
    for r in records:
        ts = r.token_t
        for a, b in zip(ts, ts[1:]):
            if t0 <= b <= t1:
                out.append(b - a)
    return out


def tokens_in(records, t0: float, t1: float) -> int:
    """Output tokens stamped inside [t0, t1]."""
    return sum(1 for r in records for t in r.token_t if t0 <= t <= t1)


def queue_wait_samples(records, t0: float, t1: float) -> list:
    """Seconds from due to slot admission of every request due in
    [t0, t1); one not admitted by t1 counts its wait so far."""
    out = []
    for r in records:
        if t0 <= r.due < t1:
            adm = r.admitted_t if r.admitted_t is not None else t1
            out.append(min(adm, t1) - r.due)
    return out
