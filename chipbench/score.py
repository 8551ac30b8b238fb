"""End-to-end arithmetic over a window's request records.

A record is a dict with at least ``ok`` (answered with status ok) and
``reached`` (its best cut reached its target, or it had none and ran the
full budget).  Every number here is taken over all requests of the
window: a request that failed, missed its target or never answered stays
in the count as a miss.
"""
from __future__ import annotations

from typing import Sequence


def solved(rec: dict) -> bool:
    return bool(rec.get("ok")) and bool(rec.get("reached"))


def solve_rate(records: Sequence[dict], window_s: float) -> float:
    """Requests that reached their target per second of the whole window."""
    return sum(1 for r in records if solved(r)) / window_s
