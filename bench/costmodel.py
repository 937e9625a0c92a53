"""The least bytes each table op must move, per key and per call.

A lookup reads its key (8 B), its two candidate buckets (2 x 16 B at four
32-bit slots) and writes its answer (1 B): 41 B; each call also reads the
whole stash (two uint32 rows).
"""
from __future__ import annotations

KEY_B = 8
ANSWER_B = 1
SLOT_B = 4


def bucket_bytes(bucket_size: int) -> int:
    return bucket_size * SLOT_B


def stash_bytes(stash_slots: int) -> int:
    return 2 * stash_slots * SLOT_B


def probe_bytes(keys: int, calls: int, *, bucket_size: int,
                stash_slots: int) -> int:
    per_key = KEY_B + 2 * bucket_bytes(bucket_size) + ANSWER_B
    return keys * per_key + calls * stash_bytes(stash_slots)


def roofline_pct(nbytes: float, device_s: float, peak_bytes_per_s: float):
    """Share of the bandwidth roofline, in %: the least time the bytes take
    at peak over the time the device spent.  None where nothing ran."""
    if device_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * (nbytes / peak_bytes_per_s) / device_s
