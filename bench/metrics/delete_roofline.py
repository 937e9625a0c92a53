"""The routed deletes' share of the HBM roofline: their least bytes
(``bench.costmodel_writes``) over the device time of ``routed_delete``."""
from bench import costmodel_writes


def read(ctx):
    return costmodel_writes.routed_roofline(ctx, "delete")
