"""The least bytes a routed insert or delete must move, per key and per
call, beside ``costmodel``'s lookup.

A write reads its key (8 B) and its two candidate buckets (2 x 16 B at four
32-bit slots), writes one slot (4 B) and its answer (1 B): 45 B.  Each call
also reads every shard's stash (two uint32 rows per shard).  Padding lanes
are not counted.
"""
from __future__ import annotations

from bench import costmodel


def write_bytes(keys: int, calls: int, *, bucket_size: int,
                stash_slots: int, n_shards: int) -> int:
    per_key = (costmodel.KEY_B + 2 * costmodel.bucket_bytes(bucket_size)
               + costmodel.SLOT_B + costmodel.ANSWER_B)
    return (keys * per_key
            + calls * n_shards * costmodel.stash_bytes(stash_slots))


def routed_roofline(ctx, kind: str):
    """The routed ``kind`` writes' share of the HBM roofline in the traced
    window: their least bytes (the keys that reached their owners, the
    ``<kind>_keys`` counter; one call per ``distributed.<kind>`` span) over
    the device time of ``routed_<kind>``, summed over the chips, against
    one chip's peak."""
    r, c = ctx["reduced"], ctx["counters"]
    t = r.program_s(rf"routed_{kind}\b")
    calls = sum(1 for name, _s, _e in r.spans
                if name == f"distributed.{kind}")
    if t <= 0 or not calls:
        return None
    nbytes = write_bytes(c[f"{kind}_keys"], calls,
                         bucket_size=c["bucket_size"],
                         stash_slots=c["stash_slots"],
                         n_shards=c["n_shards"])
    return costmodel.roofline_pct(nbytes, t, ctx["peaks"]["hbm_bytes_per_s"])
