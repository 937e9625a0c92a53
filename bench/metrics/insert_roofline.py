"""The routed inserts' share of the HBM roofline: their least bytes
(``bench.costmodel_writes``) over the device time of ``routed_insert``.

In ``kvfilter-4shard.ttl_churn`` the median call is a delete or a lookup
(9 of 20 calls insert), so a faster insert moves the window's keys per
second and the call tail, not ``lat_p50_ms``, the cell's only latency
metric it can be declared to move."""
from bench import costmodel_writes


def read(ctx):
    return costmodel_writes.routed_roofline(ctx, "insert")
