"""The little arithmetic the metrics share."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order statistics."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    rank = (len(ordered) - 1) * q / 100.0
    low = math.floor(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def median(values) -> float:
    return percentile(values, 50.0)


def union_length(intervals) -> float:
    """Total length covered by [start, end) intervals."""
    total, reach = 0.0, -math.inf
    for start, end in sorted(intervals):
        if end <= reach:
            continue
        total += end - max(start, reach)
        reach = end
    return total


def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, non-overlapping cover of the intervals."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def subtract(intervals, cover) -> list[tuple[float, float]]:
    """The parts of `intervals` (merged) that `cover` (merged) does not touch."""
    out = []
    cover = list(cover)
    for start, end in intervals:
        at = start
        for c_start, c_end in cover:
            if c_end <= at:
                continue
            if c_start >= end:
                break
            if c_start > at:
                out.append((at, c_start))
            at = max(at, c_end)
        if at < end:
            out.append((at, end))
    return out
