"""The least bytes each layer must move, from the cell's shapes.

These charge the work a layer has to do whatever implements it, so a
later kernel that does the same work in another way is measured against
the same yardstick.  No count is clamped: a share above 100% of the
peak means a count or a time is wrong, and is reported as read.
"""
from __future__ import annotations


def commit_bytes(batch: int, n: int) -> float:
    """One batch committed to a uint8 bitmap arena: B x n bytes of rows
    written, and the n-wide int32 counter read and written."""
    return float(batch) * n + 8.0 * n


def select_bytes(theta: int, n: int) -> float:
    """One greedy selection over a (theta, n) uint8 arena: every byte
    read at least once."""
    return float(theta) * n


def share(least_bytes: float, seconds: float, bytes_per_s: float):
    """Percent of the bandwidth roofline: the least time the bytes take
    at the peak over the time measured; None when nothing was timed."""
    if seconds <= 0:
        return None
    return 100.0 * least_bytes / bytes_per_s / seconds
