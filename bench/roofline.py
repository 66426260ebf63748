"""Byte counts of the stream engine's fold, and the roofline share they
give against the peaks of ``bench/peaks.json``.

The counts are the least any implementation must move or compute, from the
shapes alone, so a share of the roofline built on them can only be
understated, never read over 100% by a faster implementation.
"""
from __future__ import annotations

#: Bytes a fold reads per event: its value (f32), its cell (i32) and its
#: mask (bool), each once. Writes into the reservoir are not counted.
FOLD_BYTES_PER_EVENT = 4 + 4 + 1


def fold_bytes(events: int) -> int:
    """Least bytes a reservoir fold of ``events`` events moves."""
    return FOLD_BYTES_PER_EVENT * int(events)


def peak(peaks: dict, device_kind: str) -> dict:
    """The peak row of ``device_kind``; a device missing from the table is
    an error, not a default."""
    try:
        return peaks["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json") from None


def share_pct(min_seconds: float, seconds: float) -> float:
    """Roofline share: the least time over the measured time, in %."""
    return 100.0 * min_seconds / seconds
