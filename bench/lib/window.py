"""Arithmetic over the measured window: rates and shares of time.

Every number here is taken over the whole window: a rate is all the work
completed in it over its whole length, and a share of time counts every
chip for the whole window.
"""

from __future__ import annotations

from typing import Iterable, Sequence


def rate(work: float, seconds: float) -> float:
    """Work per second over a window of ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"a window of {seconds} s has no rate")
    return work / seconds


def union_length(intervals: Iterable[tuple[float, float]],
                 lo: float, hi: float) -> float:
    """Length of the union of ``intervals``, clipped to ``[lo, hi]``: an
    interval that lies wholly outside it adds nothing."""
    clipped = sorted((max(a, lo), min(b, hi)) for a, b in intervals)
    total, end = 0.0, lo
    for a, b in clipped:
        if b <= max(a, end):
            continue
        total += b - max(a, end)
        end = b
    return total


def idle_share(busy: dict, chips: Sequence, lo: float, hi: float) -> float:
    """Share (0..1) of the chip-seconds of ``[lo, hi]`` in which a chip ran
    nothing; ``busy`` maps a chip to its busy intervals (absent: idle)."""
    span = (hi - lo) * len(chips)
    if span <= 0:
        raise ValueError("an empty window has no idle share")
    used = sum(union_length(busy.get(c, ()), lo, hi) for c in chips)
    return 1.0 - used / span


def busy_by_chip(jobs: Iterable[dict]) -> dict:
    """Chip -> [(start, end)] of the jobs that ran on it."""
    out: dict = {}
    for job in jobs:
        for c in job["chips"]:
            out.setdefault(c, []).append((job["start"], job["end"]))
    return out
